"""Continuous-depth mode for the unified LM — the paper's technique at
framework scale (DESIGN.md §4).

A pre-norm residual stack is read as the Euler discretization of a depth
ODE with piecewise-constant parameters theta(s) (paper Eq. 1 allows
s-dependent parameters):

    f(s, h) = n_groups * (group_apply(theta(floor(s * n_groups)), h) - h)

Euler with K = n_groups steps reproduces the discrete network EXACTLY
(tested); K < n_groups trades NFE (~ layer evaluations) for accuracy, and a
HyperEuler correction g_omega — trained by residual fitting against the
full-depth trajectory (the LM analogue of the paper's dopri5 ground truth)
— recovers most of the lost accuracy. This transplants the paper's CNF
result (2-NFE sampling) to LM inference.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.core import FixedGrid, Integrator, SolveStats, get_tableau, with_initial
from repro.core.residual import combined_loss
from repro.models.lm import (
    ZERO_AUX, _embed, _readout, block_apply, dtype_of, group_layout,
)
from repro.nn.module import truncated_normal_init


def _group_apply(params, cfg: ArchConfig, gp, h):
    pattern, _, _ = group_layout(cfg)
    aux = ZERO_AUX()
    for i, kind in enumerate(pattern):
        h, aux = block_apply(gp[f"b{i}"], cfg, kind, h, aux)
    return h


def depth_field(cfg: ArchConfig):
    """Vector field ``f(params, s, h)`` over the residual stream (full
    sequence). The weights are an argument, not a closure, so a jit cell
    that evaluates the field takes them as an operand instead of baking
    them into its program as constants.

    ``s`` may be a scalar or a per-sample ``(B,)`` row (multi-rate solves,
    core/integrate.py ``solve_multirate``): group selection is by depth, so
    per-sample depths gather per-sample group weights via a vmap over the
    batch axis (each sample keeps its singleton batch dim so the block
    stack sees its native (B, S, d) rank)."""
    _, n_groups, _ = group_layout(cfg)

    def f(params, s, h):
        if jnp.ndim(s):
            return jax.vmap(lambda si, hi: f(params, si, hi[None])[0])(
                jnp.reshape(s, (-1,)), h)
        idx = jnp.clip(jnp.floor(s * n_groups).astype(jnp.int32), 0,
                       n_groups - 1)
        gp = jax.tree_util.tree_map(lambda p: p[idx], params["groups"])
        h_out = _group_apply(params, cfg, gp, h)
        return (n_groups * (h_out - h)).astype(h.dtype)

    return f


def discrete_depth_trajectory(params, cfg: ArchConfig, tokens: jnp.ndarray,
                              frontend: Optional[jnp.ndarray] = None):
    """Residual-stream states at every group boundary — the 'exact'
    solution checkpoints for hypersolver fitting (paper Sec. 3.2; ground
    truth here is the deployed full-depth network itself).

    Deliberately NOT an Integrator solve: Euler at K = n_groups matches
    this walk only up to the eps*(n*(h_out-h)) recombination rounding,
    and in a bf16 residual stream that per-step ulp noise is the same
    order as the residuals g_omega fits. Ground truth must emit the
    group outputs bit-exactly; only the trajectory stacking is shared
    with the engine. Returns (n_groups+1, B, S, d).
    """
    h0 = _embed(params, cfg, tokens)
    if frontend is not None:
        from repro.nn.module import dense
        fe = dense(params["patch_proj"], frontend.astype(h0.dtype))
        h0 = jnp.concatenate([fe, h0], axis=1)

    def body(h, gp):
        h_out = _group_apply(params, cfg, gp, h)
        return h_out, h_out

    _, traj = jax.lax.scan(body, h0, params["groups"])
    return with_initial(h0, traj)


# --------------------------------------------------- g_omega for the LM ----

def lm_g_init(key, cfg: ArchConfig, rank: int = 64, n_fourier: int = 8,
              param_dtype=None):
    pd = param_dtype or dtype_of(cfg.param_dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    return {
        "w_h": truncated_normal_init(k1, (d, rank), d ** -0.5, pd),
        "w_dh": truncated_normal_init(k2, (d, rank), d ** -0.5, pd),
        "w_s": truncated_normal_init(k3, (2 * n_fourier + 1, rank), 0.3, pd),
        # zero-init readout: correction starts at exactly 0 (pure base solver)
        "w_out": jnp.zeros((rank, d), pd),
    }


def _fourier(s, n: int, dtype):
    """Fourier depth features; ``s`` may be a scalar (fixed-K serving) or
    per-sample batched ``(B,)`` (multi-rate meshes and the refinery's
    residual-capture cell hand every row its own depth)."""
    s = jnp.asarray(s, jnp.float32)
    ks = jnp.arange(1, n + 1, dtype=jnp.float32)
    ang = 2 * jnp.pi * ks * s[..., None]            # (..., n)
    feats = jnp.concatenate([jnp.sin(ang), jnp.cos(ang), s[..., None]],
                            axis=-1)                # (..., 2n + 1)
    return feats.reshape(s.shape + (2 * n + 1,)).astype(dtype)


def lm_g_apply(gp, eps, s, x, h, dh):
    """Correction net: rank-r MLP over (h, dh, s). MAC cost 3*d*r per token
    — negligible next to the ~12 d^2 block cost (paper Sec. 6 overhead)."""
    del eps, x
    nf = (gp["w_s"].shape[0] - 1) // 2  # w_s: (2*n_fourier + 1, rank)
    sf = _fourier(s, nf, h.dtype) @ gp["w_s"].astype(h.dtype)
    if jnp.ndim(s):
        # batched depth row: align sf's leading sample axis with h's by
        # inserting singleton token axes — (B, r) -> (B, 1..., r)
        sf = jnp.reshape(
            sf, sf.shape[:-1] + (1,) * (h.ndim - sf.ndim) + sf.shape[-1:])
    pre = (h @ gp["w_h"].astype(h.dtype)
           + dh.astype(h.dtype) @ gp["w_dh"].astype(h.dtype) + sf)
    return (jnp.tanh(pre) @ gp["w_out"].astype(h.dtype)).astype(h.dtype)


# ----------------------------------------------- flow head for the LM ----

def lm_flow_init(key, cfg: ArchConfig, rank: int = 64, n_fourier: int = 8,
                 param_dtype=None):
    """Flow-net params for the K=0 tier (core/flowhead.py): the SAME
    rank-r architecture as g_omega — flow and correction fit the same
    eps^{p+1}-scaled residual target, so one net family serves both
    sites. Zero-init readout means the flow starts as EXACTLY one
    full-span Euler step."""
    return lm_g_init(key, cfg, rank=rank, n_fourier=n_fourier,
                     param_dtype=param_dtype)


def lm_flow_apply(fp, eps, s, z, dz, order: int = 1):
    """LM solution operator F(z(s)) -> z(s+eps) — ``make_flow_apply``
    over the ``lm_g_apply`` net (DepthModel.flow_apply signature)."""
    from repro.core.flowhead import flow_combine

    return flow_combine(eps, z, dz, lm_g_apply(fp, eps, s, None, z, dz),
                        order=order)


# ----------------------------------------------------------- inference ----

def bind_lm_g(g_params):
    """Close LM g_omega over its params to the core Correction signature."""
    return lambda eps, s, z, dz: lm_g_apply(g_params, eps, s, None, z, dz)


def lm_integrator(solver: str = "euler", g_params: Any = None,
                  fused: bool = False) -> Integrator:
    """The serving Integrator for the LM depth ODE. ``solver`` may carry a
    ``hyper_`` prefix (``hyper_euler`` == euler base + correction, which
    then requires ``g_params`` — a hyper solver silently downgraded to its
    base would misreport agreement/benchmark numbers)."""
    if solver.startswith("hyper_"):
        if g_params is None:
            raise ValueError(
                f"solver {solver!r} needs a trained correction: pass "
                "g_params (serve CLI: --g-ckpt)")
        base = solver[len("hyper_"):]
    else:
        base = solver
    g = bind_lm_g(g_params) if g_params is not None else None
    return Integrator(tableau=get_tableau(base), g=g, fused=fused)


def apply_tail(params, cfg: ArchConfig, h):
    """The discrete tail layers + readout shared by every LM serving path
    (full-K scoring, the engine's readout, and reference solves)."""
    pattern, _, tail = group_layout(cfg)
    aux = ZERO_AUX()
    for i in range(tail):
        h, aux = block_apply(params["tail"][f"t{i}"], cfg, pattern[i], h, aux)
    return _readout(params, cfg, h)


def lm_forward_cdepth(params, cfg: ArchConfig, tokens: jnp.ndarray, K: int,
                      solver: str = "euler", g_params: Any = None,
                      frontend: Optional[jnp.ndarray] = None,
                      with_stats: bool = False):
    """Full-sequence scoring with a K-step (hyper)solved depth integration.

    K == n_groups with solver='euler', g=None reproduces lm_forward exactly
    (up to tail layers, which are always applied discretely).

    ``with_stats=True`` additionally returns per-sample ``SolveStats`` (NFE
    = stages * K for every row of the batch; the multi-rate engine in
    launch/engine.py adds its probe cost on top).
    """
    pattern, n_groups, tail = group_layout(cfg)
    h = _embed(params, cfg, tokens)
    if frontend is not None:
        from repro.nn.module import dense
        fe = dense(params["patch_proj"], frontend.astype(h.dtype))
        h = jnp.concatenate([fe, h], axis=1)
    f = partial(depth_field(cfg), params)
    integ = lm_integrator(solver, g_params)
    grid = FixedGrid.over(0.0, 1.0, K)
    h = integ.solve(f, h, grid, return_traj=False)
    logits = apply_tail(params, cfg, h)
    if not with_stats:
        return logits
    B = tokens.shape[0]
    stats = SolveStats(
        nfe=jnp.full((B,), integ.tableau.stages * K, jnp.int32),
        K=jnp.full((B,), K, jnp.int32),
        err_probe=jnp.zeros((B,), jnp.float32),
        probe_nfe=0,
    )
    return logits, stats


def depth_probe(params, cfg: ArchConfig, tokens: jnp.ndarray, controller,
                solver: str = "euler", g_params: Any = None,
                frontend: Optional[jnp.ndarray] = None):
    """Cheap per-request error probe over the LM depth ODE.

    Embeds the prompt and lets ``controller`` (core/controllers.py) pick a
    per-sample mesh length from one probe step of the depth field. Returns
    a ``Probe`` (K, err, nfe, dz0) — the serving engine snaps K to its
    buckets and reuses dz0 as the solve's first stage."""
    h = _embed(params, cfg, tokens)
    if frontend is not None:
        from repro.nn.module import dense
        fe = dense(params["patch_proj"], frontend.astype(h.dtype))
        h = jnp.concatenate([fe, h], axis=1)
    f = partial(depth_field(cfg), params)
    integ = lm_integrator(solver, g_params)
    return controller.select(integ, f, h, (0.0, 1.0))


def cdepth_residual_loss(params, g_params, cfg: ArchConfig,
                         tokens: jnp.ndarray, K: int,
                         base_solver: str = "euler"):
    """Residual-fitting loss for the LM hypersolver at mesh length K.

    Ground truth = full-depth discrete trajectory subsampled at the K-mesh
    (requires n_groups % K == 0).
    """
    _, n_groups, _ = group_layout(cfg)
    assert n_groups % K == 0, (n_groups, K)
    stride = n_groups // K
    traj_full = discrete_depth_trajectory(params, cfg, tokens)
    traj = traj_full[::stride]  # (K+1, B, S, d)
    f = partial(depth_field(cfg), params)
    g = lambda eps, s, z, dz: lm_g_apply(g_params, eps, s, None, z, dz)
    integ = Integrator(tableau=get_tableau(base_solver), g=g)
    grid = FixedGrid.over(0.0, 1.0, K)
    return combined_loss(integ, f, traj, grid, residual_weight=1.0)
