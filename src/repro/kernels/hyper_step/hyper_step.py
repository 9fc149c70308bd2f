"""Fused hypersolver update with RUNTIME step sizes (paper Eq. 3 + Eq. 5):

    z_{k+1}[i] = where(active[i],
                       z_k[i] + eps[i] * sum_j b_j r_j[i]
                              + eps[i]^{p+1} * g[i],
                       z_k[i])

One kernel pass fuses the b-weighted stage combination of ANY explicit
tableau with the eps^{p+1} correction AND the multi-rate freeze mask: the
state and each stage are read once and the new state written once, instead
of the ``stages + 3`` HBM round-trips of the unfused leaf-wise
lincomb/axpy/axpy/where sequence. The update is purely memory-bound, so
this traffic reduction is the whole optimization on TPU (interpret mode on
CPU).

Step sizes are *runtime operands*, not compile-time constants: the
per-sample ``eps`` row, its derived ``eps^{p+1}`` correction scale, and the
``active`` mask row ride in SMEM via ``pltpu.PrefetchScalarGridSpec`` and
are looked up per batch row with a scalar read — so one compiled kernel
serves every step size (scalar, traced, per-sample multi-rate) with no
respecialization.

Layout is batch-major: each sample's flattened state is a ``(R, 128)``
lane-aligned plane and the operands stack to ``(B, R, 128)``. Tiles are
``(1, BR, 128)`` VMEM blocks — rows of one tile belong to a single sample,
so samples share nothing but the prefetch lookup, which is what makes the
kernel trivially shardable over the batch axis (launch/mesh.py).
Accumulation is fp32 regardless of the storage dtype.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8        # fp32 sublane quantum: R is padded to a multiple of this
LANES = 128
MAX_BLOCK_ROWS = 256  # VMEM block rows per tile (1 x 256 x 128 fp32 = 128 KiB)


def _rk_kernel(eps_ref, epsp_ref, act_ref, *refs,
               b: Tuple[float, ...], with_g: bool):
    """refs = (z, r_0..r_{S-1}, [g], out); eps/epsp/act are SMEM prefetch
    rows indexed by the batch grid coordinate. The stage count is static,
    so the combination loop fully unrolls into VPU fma chains; the step
    size is a runtime scalar broadcast into them."""
    z_ref, o_ref = refs[0], refs[-1]
    stage_refs = refs[1:1 + len(b)]
    i = pl.program_id(0)                      # batch row of this tile
    eps = eps_ref[i]
    z32 = z_ref[...].astype(jnp.float32)
    out = z32
    for bj, r_ref in zip(b, stage_refs):
        if bj != 0.0:
            out += (eps * bj) * r_ref[...].astype(jnp.float32)
    if with_g:
        g_ref = refs[1 + len(b)]
        out += epsp_ref[i] * g_ref[...].astype(jnp.float32)
    out = jnp.where(act_ref[i] != 0, out, z32)
    o_ref[...] = out.astype(o_ref.dtype)


def rk_update_batched(z: jnp.ndarray, stages: Sequence[jnp.ndarray],
                      g: Optional[jnp.ndarray],
                      eps_row: jnp.ndarray, epsp_row: jnp.ndarray,
                      active_row: jnp.ndarray, b: Tuple[float, ...],
                      interpret: bool = False):
    """z, stages[j], g: (B, R, 128) batch-major views; eps_row, epsp_row:
    (B,) float32; active_row: (B,) int32. Returns z_next of z.dtype."""
    assert len(stages) == len(b), (len(stages), b)
    B, R, L = z.shape
    assert L == LANES and R % SUBLANES == 0, (B, R, L)
    br = min(R, MAX_BLOCK_ROWS)
    assert R % br == 0, (R, br)
    operands = [z, *stages] + ([g] if g is not None else [])
    # index maps under PrefetchScalarGridSpec receive the prefetch refs as
    # trailing args; the data tiling ignores them (values, not indices).
    spec = pl.BlockSpec((1, br, LANES), lambda i, j, *_: (i, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, R // br),
        in_specs=[spec] * len(operands),
        out_specs=spec,
    )
    return pl.pallas_call(
        functools.partial(_rk_kernel, b=tuple(b), with_g=g is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        interpret=interpret,
        name="fused_rk_update",
    )(eps_row, epsp_row, active_row, *operands)
