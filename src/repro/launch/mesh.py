"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.

Every mesh here has ``Auto`` axes: the sharding rules
(distributed/sharding.py) and the serving pools leave placement inside a
program to the compiler. ``jax.make_mesh`` defaults to ``Explicit`` axes,
under which an un-annotated gather (the embedding lookup, a slot-pool
scatter) is a ``ShardingTypeError``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is an
    outer data-parallel axis crossing DCN (only gradient all-reduce)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CPU sharding-semantics tests (run in a subprocess with
    a forced host device count — never in the main test process)."""
    return auto_mesh((n_data, n_model), ("data", "model"))


def make_serving_mesh(n_devices: int):
    """Pure data-parallel serving mesh: ``n_devices`` chips on one 'data'
    axis — the axis the in-flight slot pool shards over (there is no model
    axis at inference; the depth scan is local per slot). This is what
    ``launch/serve.py --mesh N`` builds."""
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if n_devices > jax.device_count():
        raise ValueError(
            f"--mesh {n_devices} asks for more devices than visible "
            f"({jax.device_count()}); on CPU force virtual devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return auto_mesh((n_devices,), ("data",))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch dimension shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def sharded_solve(integ, f, z0, grid, *, mesh, **solve_kwargs):
    """Run ``Integrator.solve`` data-parallel over ``mesh``: the leading
    batch axis of the state (and of a batched ``grid.eps``) shards over the
    mesh's data axis ('data', the 'pod' outer axis being gradient-only),
    the depth scan stays local to each shard — the runtime-eps fused kernel
    looks its per-row step size up from prefetched SMEM, so batch rows
    share nothing and the solve emits no collectives.

    Thin policy layer over ``integ.solve(mesh=...)``: picks the batch axis
    from the mesh and checks divisibility up front (shard_map's own error
    is about block shapes, not requests). The slot-axis sibling for the
    in-flight scheduler's segment solve is ``sharded_segment`` below."""
    import jax.numpy as jnp
    axis = "data"
    B = jax.tree_util.tree_leaves(z0)[0].shape[0]
    n = mesh.shape[axis]
    if B % n:
        raise ValueError(
            f"batch {B} does not divide the '{axis}' mesh axis ({n}); pad "
            "or re-bucket the request batch (launch/engine.py max_batch)")
    if jnp.ndim(grid.eps) not in (0, 1):
        raise ValueError(f"grid.eps must be scalar or (B,), got "
                         f"ndim={jnp.ndim(grid.eps)}")
    return integ.solve(f, z0, grid, mesh=mesh, batch_axis=axis,
                       **solve_kwargs)


def sharded_segment(integ, field_of, params, xs, carry, seg, *, mesh,
                    s0=0.0, slot_axis: str = "data"):
    """Slot-axis-sharded segment advance WITH per-slot conditioning: the
    multi-device twin of ``Integrator.solve_segment(mesh=)`` for fields
    that condition on the request input (``field_of(params, x)`` —
    launch/engine.py DepthModel adapters).

    ``Integrator.solve_segment(mesh=)`` shards the SegmentCarry rows but
    treats whatever ``f`` closes over as replicated — wrong for per-slot
    conditioning (a field closed over the FULL ``xs`` rows would see B
    conditioning rows against B/n state rows inside a shard). This helper
    threads ``xs`` through the same ``shard_map``, and the weights
    ``params`` with it, replicated, so ``field_of`` is rebuilt per shard
    from the weights and exactly its slots' conditioning rows. Returns
    ``(carry', finished)`` like ``solve_segment``; everything stays
    slot-major and collective-free.

    Thin wrapper over the one shard_map plumbing in
    ``Integrator._solve_segment_sharded`` (shared with
    ``solve_segment(mesh=)``, so the divisibility policy — a remedy-
    naming error up front, like ``sharded_solve`` — and the spec layout
    cannot diverge between the two entry points)."""
    return integ._solve_segment_sharded(
        None, carry, seg, s0, mesh, slot_axis, field_of=field_of,
        cond=xs, params=params)


def sharded_segment_cell(integ, field_of, seg, *, mesh, s0=0.0,
                         slot_axis: str = "data", donate: bool = True,
                         g_apply=None):
    """The donated jit compilation of ``sharded_segment``: one
    ``(params, xs, z, k, Ks, eps, fs) -> (z', fs', meta)`` cell per
    ``(shape, seg, mesh)`` with the pool-sized carry buffers (z, fs)
    donated, exactly like the single-device ``Integrator.segment_cell``
    — sharding changes which device owns which slot rows, never the
    donation contract or the stacked ``[k'; finished]`` retire meta. The
    serving loop (launch/scheduler.py) calls the two interchangeably.
    ``g_apply`` appends the hot-swappable correction-params operand
    (replicated across the mesh), exactly as on the single-device cell."""
    return integ.segment_cell(field_of, seg, s0=s0, mesh=mesh,
                              slot_axis=slot_axis, donate=donate,
                              g_apply=g_apply)
