"""Unified batched integration engine — every solve in the repo goes here.

``Integrator`` subsumes the three integration paths the codebase grew
(``solvers.odeint_fixed``, ``HyperSolver.odeint`` and the per-model scan
loops): one scan-native engine that

  * works on arbitrary pytree states (a CNF's ``(z, logp)`` tuple, the LM
    residual stream, image feature maps) — all linear algebra is leaf-wise;
  * composes with ``jax.jit`` / ``jax.vmap`` / ``jax.grad`` — the mesh walk
    is a single ``lax.scan`` whose unrolled HLO is O(1) in K;
  * supports *batched step sizes*: ``grid.eps`` may be an array with a
    leading batch axis (per-sample eps for multi-rate serving — each row of
    the batch integrates its own mesh), broadcast leaf-wise against the
    state;
  * emits the dense trajectory (leading axis K+1, including z0) or the
    terminal state only;
  * optionally rematerializes each step under reverse-mode AD
    (``checkpoint=True``) so trajectories of long meshes backprop in O(K)
    memory instead of O(K * stages);
  * routes the update through the fused Pallas ``hyper_step`` kernel
    (``fused=True``): the b-weighted stage combination, the eps^{p+1}
    correction term, AND the multi-rate freeze mask collapse into one
    memory pass per leaf, for every base tableau — the update is
    memory-bound, so this is the serving hot path. Step sizes are RUNTIME
    kernel operands (scalar-prefetch SMEM rows, kernels/hyper_step): a
    Python float, a traced scalar, and a per-sample ``(B,)`` eps row all
    hit the same compiled kernel, so multi-rate serving never recompiles
    per step size and never falls off the fused path. The only surviving
    fallback is odd state dtypes (see ``fused_available``);
  * integrates under a step controller (``controller=``,
    core/controllers.py): a cheap probe picks a per-sample mesh length,
    the probe's first stage is reused, and the solve reports per-sample
    NFE counts (``SolveStats``) — the error-control layer multi-rate
    serving (launch/engine.py) builds on. ``solve_multirate`` is the same
    masked scan with externally supplied per-sample mesh lengths (the
    serving engine packs mixed-K batches straight into it);
  * runs data-parallel under a device mesh (``solve(mesh=...)``): the
    leading batch axis shards over the mesh's data axis via ``shard_map``
    and the depth scan stays local — batch rows share nothing (the
    runtime-eps kernel looks its coefficients up per row), so the mesh
    walk needs no cross-device communication.

The hypersolver update implemented for tableau psi and correction g
(paper Eq. 3 + Eq. 5, Poli et al. 2020):

    z_{k+1} = z_k + eps * sum_j b_j r_j + eps^{p+1} * g(eps, s_k, z_k, r_0)

Controller/engine architecture (error-controlled multi-rate serving)::

    core/tableaus.py      Tableau (+ b_err embedded weights)
          |
    core/controllers.py   embedded_step / error_ratio / step_factor
          |                 FixedController | EmbeddedErrorController |
          |                 HypersolverResidualController
          |                       | per-sample K from a cheap probe
    core/integrate.py     Integrator.solve(..., controller=) -> (z, SolveStats)
          |                 masked multi-rate scan (fused in-kernel mask),
          |                 per-sample NFE counts; solve_multirate(Ks=...)
          |                 is the serving entry; solve(mesh=...) shards
          |                 the batch axis (launch/mesh.py debug/prod mesh)
          |\
          | core/adaptive.py   odeint_dopri5 = DOPRI5 accept/reject instance
          |                    of the same embedded-error path (+ vmap batch)
    launch/engine.py      MultiRateEngine: probe -> bucket snap (packing
          |                 policy only) -> mixed-K batch packing ->
          |                 per-sample-eps fused solves
    launch/scheduler.py   InflightScheduler: slot-pool continuous batching
          |                 over ``solve_segment`` (resumable SegmentCarry,
          |                 admit/retire between segments); mesh= shards
          |                 the SLOT axis (launch/mesh.py sharded_segment)
    launch/serve.py       CLI only (arch/solver/--g-ckpt/--inflight/--mesh)

User-facing docs: README.md (quickstart), docs/architecture.md (this
diagram in prose), docs/serving.md (operator guide).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core.tableaus import Tableau, get as get_tableau

Pytree = Any
VectorField = Callable[[jnp.ndarray, Pytree], Pytree]
# g(eps, s, z, dz) -> correction pytree shaped like z; dz = f(s, z) is the
# first RK stage, passed for free reuse (paper feeds g the concat [z, dz, s]).
Correction = Callable[[Any, Any, Pytree, Pytree], Pytree]


# ------------------------------------------------------ leaf-wise algebra ----

def _bcast(a, leaf: jnp.ndarray):
    """Right-pad a batched scalar coefficient with singleton axes so it
    broadcasts against ``leaf`` from the leading (batch) axis."""
    if isinstance(a, (int, float)):
        return a
    nd = jnp.ndim(a)
    if nd == 0:
        return a
    return jnp.reshape(a, jnp.shape(a) + (1,) * (leaf.ndim - nd))


def tree_axpy(a, x: Pytree, y: Pytree) -> Pytree:
    """y + a * x, leaf-wise; ``a`` may be scalar or batched (leading axis)."""
    return jax.tree_util.tree_map(lambda xi, yi: yi + _bcast(a, yi) * xi, x, y)


def tree_lincomb(coeffs: Sequence[float], trees: Sequence[Pytree]) -> Pytree:
    """sum_j coeffs[j] * trees[j], leaf-wise (skips exact-zero coeffs)."""
    terms = [(c, t) for c, t in zip(coeffs, trees) if c != 0.0]
    if not terms:
        return jax.tree_util.tree_map(jnp.zeros_like, trees[0])
    out = jax.tree_util.tree_map(lambda l: terms[0][0] * l, terms[0][1])
    for c, t in terms[1:]:
        out = tree_axpy(c, t, out)
    return out


def depth_like(s, z: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a depth coordinate ``s`` — scalar, or per-sample (B,) when
    integrating with batched step sizes — to ``z[..., :1]``'s shape, the
    layout fields use to concatenate depth as an extra channel."""
    s = jnp.asarray(s, z.dtype)
    if s.ndim:
        s = s.reshape(s.shape + (1,) * (z.ndim - s.ndim))
    return jnp.broadcast_to(s, z[..., :1].shape)


def with_initial(z0: Pytree, traj: Pytree) -> Pytree:
    """Prepend the initial state to a scanned trajectory, leaf-wise."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a[None], b], axis=0), z0, traj
    )


def rk_stages(f: VectorField, tab: Tableau, s, eps, z: Pytree,
              first_stage: Optional[Pytree] = None):
    """All stage evaluations r_i of an explicit tableau (paper Eq. 3).

    ``stages[0] == f(s, z)``, which hypersolvers reuse as a free input to
    g_omega. ``eps`` may be batched (leading axis). A precomputed
    ``first_stage`` (e.g. a controller probe's dz, core/controllers.py)
    substitutes for stage 0, saving one vector-field evaluation."""
    stages = []
    for i in range(tab.stages):
        if i == 0:
            if first_stage is not None:
                stages.append(first_stage)
                continue
            zi = z
        else:
            zi = tree_axpy(eps, tree_lincomb(tab.a[i], stages), z)
        stages.append(f(s + tab.c[i] * eps, zi))
    return stages


def rk_psi(f: VectorField, tab: Tableau, s, eps, z: Pytree):
    """(psi, stages) where psi = sum_j b_j r_j is the RK update map."""
    stages = rk_stages(f, tab, s, eps, z)
    return tree_lincomb(tab.b, stages), stages


# Storage dtypes the runtime-eps Pallas kernel takes. Since eps became a
# runtime operand (scalar-prefetch SMEM row), step sizes can no longer
# disqualify the fused path — odd state dtypes are the only fallback left.
_FUSED_DTYPES = frozenset(("float32", "bfloat16", "float16"))


def _fusable(z: Pytree) -> bool:
    """True iff every state leaf has a dtype the fused kernel stores.
    Dtype-less leaves (Python scalars) take the jnp fallback, which
    promotes them; the kernel needs real arrays."""
    return all(hasattr(l, "dtype") and l.dtype.name in _FUSED_DTYPES
               for l in jax.tree_util.tree_leaves(z))


class OneTimeWarning:
    """Resettable one-time RuntimeWarning latch.

    A process-global module bool made warning assertions test-order-
    dependent (whichever test tripped a warning first swallowed everyone
    else's). Each warn-once site holds an instance and exposes a reset
    function that the autouse fixture in tests/conftest.py re-arms per
    test. Instances: the fused-fallback warning below, and the bucket-
    overflow snap warning in launch/engine.py."""

    __slots__ = ("warned",)

    def __init__(self) -> None:
        self.warned = False

    def warn(self, message: str, stacklevel: int = 4) -> None:
        if not self.warned:
            warnings.warn(message, RuntimeWarning, stacklevel=stacklevel)
            self.warned = True

    def reset(self) -> None:
        self.warned = False


_fused_fallback = OneTimeWarning()


def reset_fused_fallback_warning() -> None:
    """Re-arm the one-time fused-fallback RuntimeWarning (test isolation)."""
    _fused_fallback.reset()


def _nonfinite_rows(z: Pytree, like: jnp.ndarray) -> jnp.ndarray:
    """Per-slot non-finite flag: True where any inexact element of a
    slot's state row is NaN/Inf, reduced over every non-slot axis of
    every inexact leaf. Plain jnp reductions — no extra kernel trace —
    and row-wise, so it composes with the slot-sharded segment (each
    row's flag depends only on that row's data; no collective).
    ``like`` supplies the (B,) shape/backing for stateless pools."""
    flags = [jnp.any(~jnp.isfinite(l), axis=tuple(range(1, jnp.ndim(l))))
             for l in jax.tree_util.tree_leaves(z)
             if jnp.issubdtype(jnp.asarray(l).dtype, jnp.inexact)]
    if not flags:
        return jnp.zeros_like(like, dtype=bool)
    bad = flags[0]
    for f in flags[1:]:
        bad = bad | f
    return bad


class SegmentCarry(NamedTuple):
    """Resumable per-slot state of a segmented multi-rate solve.

    One row per *slot* (leading axis B on every array/leaf). A slot is a
    request mid-integration: ``z`` its current state, ``k`` the next depth
    step it will take, ``Ks`` its target mesh length, ``eps`` its step
    size. ``first_stage`` optionally carries the admission probe's
    ``dz0 = f(s0, z0)`` rows, substituted as stage 0 exactly while a slot
    is still at ``k == 0`` — the same probe reuse ``solve_multirate`` gets
    via its ``first_stage=`` argument, so segment-wise serving loses no
    NFE accounting honesty.

    The carry is a plain pytree: it jits, donates, and scatters (slot
    refill is a leaf-wise ``.at[idx].set``). ``Integrator.segment_cell``
    is the donation-ready compilation: ``z`` and ``first_stage`` (the two
    pool-sized buffers) are donated into the segment and alias in place
    as its outputs — a caller holding the carry owns at most TWO logical
    buffers per pool, the in-flight one (dead to the host once the
    segment is dispatched) and the resident one (the previous segment's
    outputs, which retire/refill scatter into). A retired/empty slot is
    encoded as ``Ks == 0``: ``k < Ks`` is then always False, so the fused
    freeze mask keeps its rows inert at zero bookkeeping cost —
    occupancy is data, never a shape, which is what keeps one
    ``(shape, seg)`` compilation serving every admission pattern.

    Every field is SLOT-MAJOR (leading axis B), deliberately: the carry
    splits row-wise over a device mesh (``solve_segment(mesh=)`` /
    ``launch/mesh.py::sharded_segment``), so a multi-device slot pool is
    the same pytree sharded — nothing in the layout distinguishes one
    chip's pool from a shard of a bigger one.
    """

    z: Pytree                       # per-slot state, leading slot axis B
    k: jnp.ndarray                  # (B,) int32 — next depth-step index
    Ks: jnp.ndarray                 # (B,) int32 — target mesh lengths (0 = empty)
    eps: jnp.ndarray                # (B,) — per-slot step sizes
    first_stage: Optional[Pytree]   # probe dz0 rows, used only at k == 0


def make_segment_carry(z0: Pytree, Ks, span, *,
                       first_stage: Optional[Pytree] = None) -> SegmentCarry:
    """Fresh carry for a slot batch: every slot at ``k = 0`` with
    ``eps_i = (s1 - s0) / Ks[i]`` — the identical arithmetic of
    ``solve_multirate``, so a segment-driven solve is step-for-step the
    same mesh. ``Ks[i] == 0`` marks an empty slot (eps set to 1.0 so no
    inf/NaN rides along in the frozen rows)."""
    s0, s1 = span
    Ks = jnp.asarray(Ks, jnp.int32)
    eps = jnp.asarray(s1 - s0) / jnp.maximum(Ks, 1)
    eps = jnp.where(Ks > 0, eps, jnp.ones_like(eps))
    return SegmentCarry(z=z0, k=jnp.zeros_like(Ks), Ks=Ks, eps=eps,
                        first_stage=first_stage)


@dataclasses.dataclass(frozen=True)
class SolveStats:
    """Per-sample accounting from a controller-driven solve.

    ``nfe`` includes the controller's probe cost; ``K`` is the per-sample
    mesh length the controller selected; ``err_probe`` its local-error
    estimate (0 for FixedController)."""

    nfe: jnp.ndarray        # (B,) int32 — vector-field evals incl. probe
    K: jnp.ndarray          # (B,) int32 — selected mesh lengths
    err_probe: jnp.ndarray  # (B,) float32 — probe local-error estimate
    probe_nfe: int          # per-sample probe cost included in ``nfe``


# ------------------------------------------------------------- the engine ----

@dataclasses.dataclass(frozen=True)
class Integrator:
    """A base explicit-RK tableau, optionally paired with a hypersolver
    correction ``g`` of matching order (paper Sec. 3) and a fused Pallas
    update path.

    ``fused=True`` collapses the whole per-step state update — the
    b-weighted stage combination, the eps^{p+1} correction, and the
    multi-rate freeze mask — into a single Pallas kernel pass per leaf
    (kernels/hyper_step): one read of each stage and one write of the state
    instead of ``stages + 3`` passes. The kernel takes eps at RUNTIME
    (scalar-prefetch SMEM rows), so scalar, traced, and per-sample batched
    step sizes all fuse through one compilation; only odd state dtypes
    (outside ``_FUSED_DTYPES``) fall back to the jnp path.
    """

    tableau: Tableau
    g: Optional[Correction] = None
    fused: bool = False

    @property
    def order(self) -> int:
        return self.tableau.order

    @property
    def name(self) -> str:
        base = self.tableau.name
        return f"hyper_{base}" if self.g is not None else base

    def with_tableau(self, tab: Union[str, Tableau]) -> "Integrator":
        """Swap the base tableau, keeping g (paper Sec. 4.1: an alpha-family
        hypersolver evaluated under sibling tableaus without finetuning)."""
        tab = get_tableau(tab) if isinstance(tab, str) else tab
        return dataclasses.replace(self, tableau=tab)

    def nfe(self, K: int) -> int:
        """Vector-field evaluations over K steps (g counted separately as
        overhead, paper Sec. 6)."""
        return self.tableau.stages * K

    def fused_available(self, eps=None, z: Optional[Pytree] = None) -> bool:
        """True iff the fused Pallas kernel path will actually run — the
        structured twin of the one-time fallback warning, for serving
        configs to assert the kernel is in play. ``eps`` is accepted for
        interface stability but no longer gates anything: the runtime-eps
        kernel fuses scalar, traced, AND per-sample batched step sizes.
        Pass the state (``z=``) to also vet its dtypes."""
        del eps  # runtime operand now — any step-size pattern fuses
        return self.fused and (z is None or _fusable(z))

    # ------------------------------------------------------------- step ----
    def step(self, f: VectorField, s, eps, z: Pytree,
             first_stage: Optional[Pytree] = None,
             active: Optional[jnp.ndarray] = None):
        """One (hyper)solved step. Returns (z_next, psi, dz).

        ``eps`` may be a Python float, a traced scalar, or a per-sample
        ``(B,)`` row (then all state leaves carry the leading batch axis).
        ``active`` is an optional ``(B,)`` mask row: inactive samples keep
        ``z`` (the multi-rate freeze) — applied inside the fused kernel at
        zero extra memory passes, or as a trailing leaf-wise ``where`` on
        the jnp path.

        ``psi`` (the b-weighted stage combination) is lazy: on the fused
        path the kernel already produced the combined update, so psi is
        returned as ``None`` rather than re-running ``stages`` leaf-wise
        passes nobody consumes — the serving hot loop only uses z_next.

        ``first_stage`` substitutes a precomputed f(s, z) for stage 0
        (probe reuse — see core/controllers.py)."""
        tab = self.tableau
        stages = rk_stages(f, tab, s, eps, z, first_stage=first_stage)
        dz = stages[0]
        corr = self.g(eps, s, z, dz) if self.g is not None else None
        use_kernel = self.fused and _fusable(z)
        if self.fused and not use_kernel:
            _fused_fallback.warn(
                "Integrator(fused=True): state dtypes outside the kernel "
                f"set {sorted(_FUSED_DTYPES)}; falling back to the "
                "leaf-wise jnp update path for this solve.")
        if use_kernel:
            from repro.kernels.hyper_step.ops import fused_rk_update
            # zero-b stages never reach the kernel: each operand costs a
            # full HBM read per step, the whole traffic the fusion saves
            live = tuple((bj, r) for bj, r in zip(tab.b, stages)
                         if bj != 0.0)
            b_live = tuple(bj for bj, _ in live)
            n_live = len(live)
            eps_op = eps if isinstance(eps, (int, float)) \
                else jnp.asarray(eps)
            z_next = jax.tree_util.tree_map(
                lambda zl, *rest: fused_rk_update(
                    zl, rest[:n_live],
                    rest[n_live] if corr is not None else None,
                    eps_op, b_live, tab.order, active=active),
                z, *(r for _, r in live),
                *((corr,) if corr is not None else ()))
            psi = None  # fused kernel already combined the stages
        else:
            psi = tree_lincomb(tab.b, stages)
            z_next = tree_axpy(eps, psi, z)
            if corr is not None:
                p1 = self.order + 1
                ceps = eps ** p1 if isinstance(eps, (int, float)) \
                    else jnp.asarray(eps) ** p1
                z_next = tree_axpy(ceps, corr, z_next)
            if active is not None:
                z_next = jax.tree_util.tree_map(
                    lambda a, b_: jnp.where(_bcast(active, b_), a, b_),
                    z_next, z)
        return z_next, psi, dz

    # ------------------------------------------------------------ solve ----
    def solve(
        self,
        f: VectorField,
        z0: Pytree,
        grid,
        *,
        return_traj: bool = True,
        checkpoint: bool = False,
        controller=None,
        first_stage: Optional[Pytree] = None,
        mesh=None,
        batch_axis: str = "data",
    ):
        """Integrate z' = f(s, z) over ``grid`` (a FixedGrid; ``grid.eps``
        may carry a leading batch axis for per-sample step sizes, in which
        case ``f`` receives a batched ``s`` — use ``depth_like`` to lift it
        leaf-wise; ``jax.vmap`` over (z0, eps) is the fully general path).

        Returns the dense trajectory stacked on a leading axis of length
        K+1 (including z0) when ``return_traj``, else the terminal state.
        ``checkpoint=True`` rematerializes each step under reverse-mode AD.

        With a ``controller`` (core/controllers.py), ``grid`` supplies only
        the span [s0, s0 + eps*K] (scalar eps required): the controller
        probes z0, picks a per-sample mesh length K_i, and the solve runs a
        masked multi-rate scan — sample i integrates at eps_i = span/K_i
        and freezes after K_i steps. Returns ``(result, SolveStats)`` with
        per-sample NFE counts (probe included, minus the reused first
        stage). The scan length is the controller's ``k_max``, so pack
        similar-difficulty samples together (launch/engine.py's bucketing)
        to avoid masked-step waste.

        ``first_stage`` is a precomputed f(s0, z0) (a probe's dz) reused as
        stage 0 of the first step — one NFE saved per solve.

        ``mesh`` shards the solve data-parallel: the leading batch axis of
        every state leaf (and a batched ``grid.eps``) is sharded over the
        mesh's ``batch_axis`` via ``shard_map`` and the depth scan runs
        local to each shard — batch rows share nothing, so no collective
        is ever emitted. The batch size must divide the axis size.
        """
        if mesh is not None:
            return self._solve_sharded(
                f, z0, grid, mesh, batch_axis, return_traj=return_traj,
                checkpoint=checkpoint, controller=controller,
                first_stage=first_stage)
        eps = grid.eps
        if controller is not None:
            return self._solve_controlled(f, z0, grid, controller,
                                          return_traj, checkpoint)

        def body(z, k):
            z_next, _, _ = self.step(f, grid.s0 + k * eps, eps, z)
            return z_next, (z_next if return_traj else None)

        if checkpoint:
            body = jax.checkpoint(body)
        if first_stage is None:
            zT, ys = jax.lax.scan(body, z0, jnp.arange(grid.K))
            if not return_traj:
                return zT
            return with_initial(z0, ys)
        # step 0 unrolled to consume the probe's stage; scan the rest
        z1, _, _ = self.step(f, grid.s0, eps, z0, first_stage=first_stage)
        zT, ys = jax.lax.scan(body, z1, jnp.arange(1, grid.K))
        if not return_traj:
            return zT
        return with_initial(z0, with_initial(z1, ys))

    def solve_multirate(self, f, z0: Pytree, span, Ks, k_max: int, *,
                        first_stage: Optional[Pytree] = None,
                        return_traj: bool = False,
                        checkpoint: bool = False):
        """Masked multi-rate solve over externally supplied per-sample mesh
        lengths: sample i integrates ``span`` in ``Ks[i]`` uniform steps
        (eps_i = (s1 - s0) / Ks[i]); the scan runs ``k_max`` steps and
        freezes sample i once ``k >= Ks[i]``. All z0 leaves must share a
        leading batch axis matching ``Ks``.

        This is the serving engine's entry point (launch/engine.py packs a
        mixed-K request batch straight into one call — ``Ks`` is a traced
        operand, so one compilation per (shape, k_max) serves every bucket
        mix). On the fused path the whole masked update
        ``where(k < K_i, z + eps_i*psi + eps_i^{p+1}*g, z)`` is ONE kernel
        memory pass per leaf; unfused it is ``stages + 3`` jnp passes
        (lincomb + axpy + correction axpy + freeze where).

        ``k_max`` must cover every ``Ks[i]`` — a sample whose mesh is
        longer than the scan would silently stop mid-span (checked here
        when Ks is concrete; traced callers own the invariant, as the
        engine does with ``k_max = Ks.max()``)."""
        s0, s1 = span
        Ks = jnp.asarray(Ks, jnp.int32)
        try:
            ks_hi = int(jnp.max(Ks))
        except jax.errors.ConcretizationTypeError:
            ks_hi = None
        if ks_hi is not None and ks_hi > int(k_max):
            raise ValueError(
                f"k_max={int(k_max)} truncates samples with K up to "
                f"{ks_hi}: their scan would stop mid-span")
        eps = jnp.asarray(s1 - s0) / Ks  # (B,) per-sample step sizes

        def body(z, k):
            z_next, _, _ = self.step(f, s0 + k * eps, eps, z,
                                     active=(k < Ks))
            return z_next, (z_next if return_traj else None)

        if checkpoint:
            body = jax.checkpoint(body)
        # step 0 is always active (K_i >= 1) and can reuse a probe's dz0
        # — f(s0, z0) does not depend on eps, so it is shared by every
        # sample regardless of its selected rate.
        z1, _, _ = self.step(f, s0, eps, z0, first_stage=first_stage)
        zT, ys = jax.lax.scan(body, z1, jnp.arange(1, int(k_max)))
        if not return_traj:
            return zT
        return with_initial(z0, with_initial(z1, ys))

    def solve_segment(self, f, carry: SegmentCarry, seg: int, *,
                      s0=0.0, mesh=None, slot_axis: str = "data"):
        """Advance every slot of ``carry`` by ``seg`` depth steps and
        return ``(carry', finished)`` — the resumable core of in-flight
        continuous batching (launch/scheduler.py).

        Each slot walks its own mesh: slot i steps at ``eps_i`` from
        ``s = s0 + k_i * eps_i`` and freezes (state AND counter) once
        ``k_i >= Ks_i`` — the same masked update ``solve_multirate``
        scans, so driving a batch to completion segment-by-segment is
        step-for-step identical to one ``solve_multirate`` call with the
        same ``Ks`` row. The payoff is resumability: between segments a
        caller may retire finished slots and scatter fresh requests into
        them (a new z row, ``k = 0``, a new ``Ks``/``eps``), and because
        occupancy/refill are carried as data, ONE ``(shape, seg)``
        compilation — one kernel trace on the fused path — serves every
        admission pattern with zero recompiles.

        ``finished`` is ``k >= Ks`` after the segment: True for slots
        that completed their mesh during (or before) this segment,
        including empty ``Ks == 0`` slots — callers keep their own
        occupancy mask to tell a retired slot from a fresh completion.

        ``seg`` is a static Python int (the scan length, part of the jit
        cell); ``s0`` is the shared span origin. A slot admitted with a
        probe ``first_stage`` row consumes it on its ``k == 0`` step
        only; the blend costs no extra vector-field evaluation (the
        batch-wide ``f`` call is the one ``step`` would make anyway).

        ``mesh`` shards the SLOT axis the way ``solve(mesh=)`` shards the
        batch axis: every ``SegmentCarry`` field is slot-major, so the
        carry splits row-wise over the mesh's ``slot_axis`` via
        ``shard_map`` and the ``seg``-step depth scan runs local to each
        shard — slots share nothing (occupancy, freeze masks, and step
        sizes are all per-row data), so no collective is ever emitted and
        one ``(shape, seg, mesh)`` compilation (one fused-kernel trace)
        still serves every refill pattern. The slot count must divide the
        axis size. ``f`` must be slot-local: anything it closes over
        (model params) is replicated; per-slot conditioning must shard
        WITH the carry — use ``launch/mesh.py::sharded_segment``, which
        threads the conditioning rows through the same shard_map."""
        if mesh is not None:
            return self._solve_segment_sharded(f, carry, seg, s0, mesh,
                                               slot_axis)
        z, k, Ks, eps, fs = carry
        k = jnp.asarray(k, jnp.int32)
        Ks = jnp.asarray(Ks, jnp.int32)

        def body(zk, _):
            zc, kc = zk
            active = kc < Ks
            s = s0 + kc * eps
            if fs is None:
                dz0 = None
            else:
                # fresh slots (k == 0) substitute their probe's dz row for
                # stage 0 — identical values to f(s0, z) there, reused so
                # the probe's accounting (one eval saved) stays honest.
                dz = f(s, zc)
                fresh = kc == 0
                dz0 = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(_bcast(fresh, b), a, b), fs, dz)
            z_next, _, _ = self.step(f, s, eps, zc, first_stage=dz0,
                                     active=active)
            return (z_next, jnp.where(active, kc + 1, kc)), None

        (z, k), _ = jax.lax.scan(body, (z, k), None, length=int(seg))
        return SegmentCarry(z, k, Ks, eps, fs), k >= Ks

    def _solve_segment_sharded(self, f, carry, seg, s0, mesh, slot_axis,
                               *, field_of=None, cond=None, params=None):
        """Slot-parallel segment advance: shard every carry field over
        ``slot_axis`` and run the local ``solve_segment`` per shard. Only
        the fields the segment mutates (z, k) cross back through the
        shard_map boundary — Ks/eps/first_stage pass through unchanged.

        With ``field_of``/``cond`` (launch/mesh.py::sharded_segment),
        the per-slot conditioning rows ``cond`` shard WITH the carry, the
        model weights ``params`` enter replicated (``P()``), and each
        shard's field is rebuilt as ``field_of(params, cond_local)``;
        ``f`` is ignored. Both entry points share this one plumbing so
        the divisibility policy and the spec layout cannot diverge."""
        P = jax.sharding.PartitionSpec
        tmap = jax.tree_util.tree_map
        bspec = P(slot_axis)
        n = mesh.shape[slot_axis]
        B = carry.k.shape[0]
        if B % n:
            raise ValueError(
                f"slot count {B} does not divide the '{slot_axis}' mesh "
                f"axis ({n}); size the pool as a multiple of the axis "
                "(launch/scheduler.py slots=)")
        z, k, Ks, eps, fs = carry
        threaded = cond is not None
        args = ([params, cond] if threaded else []) + [z, k, Ks, eps]
        in_specs = ([P(), bspec] if threaded else []) + \
            [tmap(lambda _: bspec, z), bspec, bspec, bspec]
        if fs is not None:
            args.append(fs)
            in_specs.append(tmap(lambda _: bspec, fs))

        def body(*ops):
            if threaded:
                params_, cond_, *ops = ops
            z_, k_, Ks_, eps_, *fs_ = ops
            local = SegmentCarry(z_, k_, Ks_, eps_,
                                 fs_[0] if fs_ else None)
            f_local = field_of(params_, cond_) if threaded else f
            out, fin = self.solve_segment(f_local, local, seg, s0=s0)
            return out.z, out.k, fin

        out_specs = (tmap(lambda _: bspec, z), bspec, bspec)
        z2, k2, fin = jax.shard_map(
            body, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
            check_vma=False)(*args)
        return SegmentCarry(z2, k2, Ks, eps, fs), fin

    def segment_cell(self, field_of, seg: int, *, s0=0.0, mesh=None,
                     slot_axis: str = "data", donate: bool = True,
                     g_apply=None):
        """The serving-loop compilation of ``solve_segment``: one jitted
        ``(params, xs, z, k, Ks, eps, fs) -> (z', fs', meta)`` cell per
        ``(shape, seg[, mesh])``, with the carry buffers DONATED.

        The donation contract (``donate_argnums``): the big per-slot
        buffers — ``z`` and the ``fs`` probe rows — are consumed by the
        call, and XLA aliases them in place as the output buffers, so
        slot state never round-trips HBM between segments. The caller
        must treat the inputs as dead the instant the cell is dispatched
        (``Array has been deleted`` on any later use) and rebind the
        returned ``(z', fs')`` as the pool's resident state; any read of
        the OLD buffers (a finished-row readout gather, a refill
        scatter) must be enqueued BEFORE the donating call. ``k``, ``Ks``
        and ``eps`` are (B,) bookkeeping rows — too small to be worth
        aliasing, and ``Ks``/``eps`` persist host-side across segments —
        so they are passed by value.

        ``meta`` is the stacked ``(3, B)`` int32
        ``[k'; finished; nonfinite]`` rows: retiring a segment costs ONE
        device->host transfer, and because jit dispatch is async the
        caller can hold ``meta`` as a future and read it a full segment
        later (the overlap loop in launch/scheduler.py). The third row
        is the per-slot non-finite quarantine flag (``_nonfinite_rows``
        over the post-segment state): a diverging slot is detected
        inside the compiled cell — no extra transfer, no extra kernel
        trace, and row-wise so it shards with the carry — and the
        scheduler force-retires it with ``status="diverged"`` instead
        of recycling poisoned state. ``fs'`` is the first_stage passthrough —
        ``solve_segment`` never mutates it, so the donated input aliases
        straight to the output; when the pool runs probeless (``fs is
        None``) the slot contributes no donated buffer and the cell
        degrades gracefully.

        ``field_of(params, xs)`` builds the slot-local vector field from
        the model weights and the per-slot conditioning rows ``xs`` (the
        launch/engine.py ``DepthModel`` adapter shape). ``params`` is a
        traced, non-donated operand — the weights are never constants of
        the compiled cell. Under ``mesh=`` the rows thread through the
        same shard_map as the carry and the weights enter it replicated
        (``_solve_segment_sharded``).

        ``g_apply`` turns the correction into a HOT-SWAPPABLE operand:
        instead of baking g's parameters into the closure (a constant of
        the compiled cell — swapping them would force a retrace), the
        cell takes an extra trailing ``gp`` pytree and binds
        ``g = g_apply(gp, eps, s, z, dz)`` inside the trace. ``gp`` is a
        traced, NON-donated input, so replacing it between segments with
        a pytree of identical treedef/shapes/dtypes reuses the same
        compilation — the params-are-inputs invariant the online refinery
        (launch/refinery.py) rests on. Any closure ``self.g`` is ignored
        on this path. The non-parametric signature and its donation
        contract are unchanged."""

        def _advance(integ, params, xs, z, k, Ks, eps, fs):
            carry = SegmentCarry(z, jnp.asarray(k, jnp.int32),
                                 jnp.asarray(Ks, jnp.int32), eps, fs)
            if mesh is None:
                out, fin = integ.solve_segment(field_of(params, xs), carry,
                                               seg, s0=s0)
            else:
                out, fin = integ._solve_segment_sharded(
                    None, carry, seg, s0, mesh, slot_axis,
                    field_of=field_of, cond=xs, params=params)
            bad = _nonfinite_rows(out.z, like=fin)
            meta = jnp.stack([out.k.astype(jnp.int32),
                              fin.astype(jnp.int32),
                              bad.astype(jnp.int32)])
            return out.z, out.first_stage, meta

        if g_apply is None:
            def run(params, xs, z, k, Ks, eps, fs):
                return _advance(self, params, xs, z, k, Ks, eps, fs)
        else:
            def run(params, xs, z, k, Ks, eps, fs, gp):
                bound = dataclasses.replace(
                    self,
                    g=lambda e, s, zz, dzz: g_apply(gp, e, s, zz, dzz))
                return _advance(bound, params, xs, z, k, Ks, eps, fs)

        return jax.jit(run, donate_argnums=(2, 6) if donate else ())

    def _solve_controlled(self, f, z0, grid, controller, return_traj,
                          checkpoint):
        """Probe, pick per-sample mesh lengths, run the masked multi-rate
        scan, and account per-sample NFE."""
        assert jnp.ndim(grid.eps) == 0, (
            "controller-driven solve derives per-sample eps itself; pass a "
            "scalar-eps grid defining the span")
        s0 = grid.s0
        s1 = s0 + grid.eps * grid.K
        probe = controller.select(self, f, z0, (s0, s1))
        result = self.solve_multirate(
            f, z0, (s0, s1), probe.K, int(controller.k_max),
            first_stage=probe.dz0, return_traj=return_traj,
            checkpoint=checkpoint)
        reused = 1 if probe.dz0 is not None else 0
        stats = SolveStats(
            nfe=(probe.nfe - reused
                 + self.tableau.stages * probe.K).astype(jnp.int32),
            K=probe.K,
            err_probe=jnp.asarray(probe.err, jnp.float32),
            probe_nfe=int(probe.nfe),
        )
        return result, stats

    def _solve_sharded(self, f, z0, grid, mesh, batch_axis, *, return_traj,
                       checkpoint, controller, first_stage):
        """Data-parallel solve: shard the leading batch axis over
        ``batch_axis``, depth scan local to each shard. Batch rows share
        nothing — the runtime-eps kernel looks its per-row coefficients up
        from prefetched SMEM — so the body emits no collectives and the
        wrapper is pure bookkeeping (specs in, specs out)."""
        P = jax.sharding.PartitionSpec
        tmap = jax.tree_util.tree_map
        bspec = P(batch_axis)
        eps_batched = jnp.ndim(grid.eps) > 0
        args = [z0, jnp.asarray(grid.eps)]
        in_specs = [tmap(lambda _: bspec, z0),
                    bspec if eps_batched else P()]
        if first_stage is not None:
            args.append(first_stage)
            in_specs.append(tmap(lambda _: bspec, first_stage))

        def body(z0_, eps_, *fs_):
            out = self.solve(
                f, z0_, grid._replace(eps=eps_), return_traj=return_traj,
                checkpoint=checkpoint, controller=controller,
                first_stage=fs_[0] if fs_ else None)
            if controller is not None:
                res, st = out
                # SolveStats is not a pytree (static probe_nfe member):
                # ship its arrays through the shard_map boundary and
                # rebuild outside.
                return res, (st.nfe, st.K, st.err_probe)
            return out

        res_spec = P(None, batch_axis) if return_traj else bspec
        out_specs = (res_spec, (bspec, bspec, bspec)) \
            if controller is not None else res_spec
        out = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                            out_specs=out_specs, check_vma=False)(*args)
        if controller is None:
            return out
        res, (nfe, K, err) = out
        return res, SolveStats(
            nfe=nfe, K=K, err_probe=err,
            probe_nfe=int(getattr(controller, "probe_nfe", 0)))


def as_integrator(
    solver, g: Optional[Correction] = None, fused: bool = False
) -> Integrator:
    """Coerce a tableau name / Tableau / Integrator / HyperSolver-like
    object (anything with .tableau/.g/.fused) into an Integrator."""
    if isinstance(solver, Integrator):
        return solver
    if isinstance(solver, str):
        return Integrator(tableau=get_tableau(solver), g=g, fused=fused)
    if isinstance(solver, Tableau):
        return Integrator(tableau=solver, g=g, fused=fused)
    if hasattr(solver, "tableau"):
        return Integrator(tableau=solver.tableau,
                          g=getattr(solver, "g", g),
                          fused=getattr(solver, "fused", fused))
    raise TypeError(f"cannot build an Integrator from {solver!r}")
