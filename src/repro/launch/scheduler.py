"""In-flight depth-continuous batching: a slot-pool scheduler over the
resumable segment solve.

The slot/segment model, against ``engine.py``'s drain loop
==========================================================

``MultiRateEngine.step()`` is a batch job: it drains the whole queue,
probes, packs by bucket, and solves each batch TO COMPLETION before any
new request gets a look. Under streaming traffic that shape loses twice:

  * **queue wait** — a request arriving just after a drain starts waits
    out the entire drain (worst case: every batch of it), even if a slot's
    worth of work would have served it immediately;
  * **masked-step waste** — a K=2 request packed next to a K=16 request
    rides the scan to k_max frozen, burning kernel passes on rows that
    finished 14 steps ago.

This module is the depth-axis analog of token-level continuous batching
from LLM serving (Orca/vLLM): where those schedulers admit and retire
sequences between *decode steps*, ``InflightScheduler`` admits and retires
requests between *depth segments* of the ODE solve. The pieces:

  * A fixed **slot pool** per request (shape, dtype) cell: ``slots`` rows
    of a resumable
    ``SegmentCarry`` (core/integrate.py) — per-slot state z, step counter
    k, target mesh length Ks, step size eps, and the admission probe's
    first stage. ``Ks == 0`` marks an empty slot; occupancy is DATA, not
    shape, so one ``(shape, seg)`` jit cell (one fused-kernel trace)
    serves every admission/refill pattern with zero recompiles.
  * A **segment** is ``seg`` masked multi-rate depth steps of the whole
    pool (``Integrator.solve_segment``) — the same fused kernel pass the
    drain engine uses, just chunked. A slot is finished exactly when
    ``k >= Ks``, which is the freeze mask the kernel already takes as a
    scalar-prefetch row.
  * Between segments, finished slots **retire** (readout -> completion
    record) and **refill** from the queue: admission probes the newcomers
    batch (padded to the pool width so the probe stays one jit cell),
    reusing the controller policy from ``launch/engine.py``
    (``make_controller`` + ``snap_to_buckets``), and scatters their rows
    into the free slots. A K=2 request admitted next to a half-done K=16
    request exits after its own ~K/seg segments instead of waiting out
    the batch.

Multi-device slot pools
-----------------------

Passing ``mesh=`` shards the SLOT axis over the mesh's data axis via
``shard_map`` (``Integrator.solve_segment(mesh=)`` /
``launch/mesh.py::sharded_segment``), the way ``Integrator.solve(mesh=)``
shards the batch axis: each device owns ``slots / n_devices`` rows of the
carry, the depth scan stays local, and no collective is ever emitted —
slots share nothing. Admission remains ONE global FIFO queue feeding the
global pool width; retire/refill between segments operates on the
gathered ``k``/``Ks`` host rows exactly as on one device. Because
occupancy is still data, one ``(shape, seg, mesh)`` jit cell (one
fused-kernel trace) serves every refill pattern per device. On the
virtual clock a segment's cost is batch-width-free, so sharding buys
capacity: n devices hold n-fold the slots at the same sequential cost
per tick.

Virtual-cost clock
------------------

The scheduler keeps a virtual clock (``self.now``) priced by a pluggable
cost oracle (``launch/oracle.py``). The default ``SequentialEvalOracle``
is the same unit as ``engine.StepReport``: SEQUENTIAL vector-field
evaluations (batch-width free — the axis an accelerator parallelizes),
where one segment costs ``tableau.stages * seg`` and an admission probe
costs the controller's ``probe_nfe``; ``RooflineOracle`` prices the same
events in predicted device-us via the analytic roofline model, making
pool width a real cost axis. Completions are stamped at the end of the
tick that retired them with only THEIR pool's probe + segment cost —
pools are concurrent cells (the PR-5 sharding semantics), so one pool's
segment never inflates another pool's latency, while ``total_cost``
still sums every pool's work as a resource ledger.
``launch/workload.py`` replays identical arrival traces against this
clock and the drain engine's, producing comparable queue wait / latency
/ waste numbers.

Choosing ``seg``: small ``seg`` = fast admission and low masked waste but
more per-segment host round-trips; large ``seg`` degenerates toward the
drain loop (``seg >= max bucket`` is exactly a drain with extra steps).
``seg`` of 2-4 with ``slots ~ max_batch`` is the useful regime.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controllers import FixedController, TierRouter
from repro.core.integrate import SegmentCarry
from repro.distributed.fault import FaultInjector, RetryPolicy
from repro.launch.engine import (
    STATUSES, DepthModel, EngineConfig, QueueFull, Request,
    bound_integrator, make_controller, next_bucket_above, prepare_model,
    probe_net_nfe, screen_probe_errors, snap_to_buckets, validate_g_swap,
)
from repro.launch.oracle import CostOracle, SequentialEvalOracle

__all__ = ["InflightScheduler", "InflightCompleted", "TickReport",
           "STATUSES", "QueueFull", "RetryPolicy", "FaultInjector"]


@dataclasses.dataclass(frozen=True)
class InflightCompleted:
    """Per-request terminal record with the latency decomposition the
    drain engine cannot express: queue wait (submit -> slot admission) and
    service (admission -> retirement), in virtual cost units.

    ``status`` is the request's terminal disposition (engine.STATUSES;
    docs/serving.md "Failure semantics"): ``ok``/``retried`` carry real
    outputs, ``diverged``/``deadline`` carry the best-effort partial
    readout (or None if the request expired while still queued), and
    ``shed`` carries None — the overload policy refused it at admission.
    ``t_admit`` is the LAST admission (a retried request re-queues and
    re-admits); ``queue_wait`` therefore spans original submission to
    final admission."""

    uid: int
    outputs: np.ndarray
    K: int                        # snapped mesh length actually integrated
    nfe: int                      # probe (net of reuse) + stages * steps,
    #                               summed over every attempt
    err_probe: float
    fused_kernel: bool
    t_submit: float
    t_admit: float
    t_done: float
    segments: int                 # pool segments this request rode
    status: str = "ok"            # terminal status (engine.STATUSES)

    @property
    def queue_wait(self) -> float:
        return self.t_admit - self.t_submit

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit


@dataclasses.dataclass(frozen=True)
class TickReport:
    """One scheduling round: admissions + at most one segment per pool."""

    cost: float = 0.0             # sequential evals this tick
    probe_cost: float = 0.0
    admitted: int = 0
    retired: int = 0              # terminal records surfaced this tick
    useful_steps: int = 0         # slot-steps that advanced a live request
    total_steps: int = 0          # slots * seg over pools that ran
    occupied_steps: int = 0       # occupied-slot-steps (live at segment start)
    quarantined: int = 0          # slots force-retired non-finite this tick
    deadline_evicted: int = 0     # slots/queued requests evicted past deadline
    requeued: int = 0             # failed slots re-queued by the retry ladder
    shed: int = 0                 # admission refusals surfaced this tick
    probe_nonfinite: int = 0      # non-finite probe errors seen at admission
    flow_served: int = 0          # requests completed on the K=0 flow tier
    escalated: int = 0            # flow failures requeued to the K ladder

    @property
    def waste_steps(self) -> int:
        """Slot-steps computed for frozen or empty rows."""
        return self.total_steps - self.useful_steps


@dataclasses.dataclass
class _PendingSegment:
    """An in-flight segment: the async ``[k'; finished; nonfinite]``
    meta future plus the host snapshots needed to account it when it
    retires."""

    meta: Any                     # (3, B) int32 device future
    k_old: np.ndarray             # k rows at launch
    occ: np.ndarray               # occupancy at launch (bool row)
    t_done: float                 # virtual completion stamp for retires


@dataclasses.dataclass
class _FlowBatch:
    """K=0 flow-tier rows staged at admission. ``outs`` stays an async
    device future until ``finalize_retired`` (same deferral contract as
    ``_RetireBatch``); host rows are snapshots of the admitted requests —
    flow rows never touch a slot, so there is nothing to free. ``xs``
    keeps the ORIGINAL request inputs (never the chaos-poisoned probe
    copies) so an escalation requeues clean data."""

    n: int                        # real rows (outs may be pow2-padded)
    outs: Any                     # flow readout rows, device future
    t_done: float                 # admission probe + flow eval, this pool
    uid: np.ndarray
    err: np.ndarray
    t_submit: np.ndarray
    t_admit: float
    deadline: np.ndarray          # np.inf = none
    attempts: np.ndarray
    xs: np.ndarray


@dataclasses.dataclass
class _RetireBatch:
    """Retiring rows staged for materialization. ``outs`` stays an async
    device future until ``finalize_retired`` — the overlap loop
    materializes AFTER dispatching the next segment, so even the readout
    transfer hides behind device work. Host rows are SNAPSHOTS, because
    admission may refill the slots before the batch is finalized."""

    idx: np.ndarray
    outs: Any                     # readout rows, device future
    t_done: float
    fused: bool
    uid: np.ndarray
    K: np.ndarray
    k_done: np.ndarray            # depth steps actually taken (== K for ok)
    err: np.ndarray
    t_submit: np.ndarray
    t_admit: np.ndarray
    segments: np.ndarray
    status: List[str]             # terminal status per row


@dataclasses.dataclass(frozen=True)
class _RetireStats:
    """Per-pool retirement accounting for one segment."""

    retired: int = 0              # rows staged terminal (any status)
    useful: int = 0
    occupied: int = 0
    quarantined: int = 0
    deadline_evicted: int = 0
    requeued: int = 0


class _SlotPool:
    """Fixed-width slot pool for one request shape: device-side carry
    (z / first_stage pytrees) + host-side bookkeeping rows (k, Ks, eps,
    uid, timestamps). All segment jit cells are pool-width, so occupancy
    never respecializes anything; the finished-row readout cells are
    pow2-gated (see ``_readout_finished``)."""

    def __init__(self, sched: "InflightScheduler", shape: Tuple[int, ...],
                 dtype: np.dtype):
        self.sched = sched
        self.shape = shape
        n = sched.slots
        self.uid = np.full((n,), -1, np.int64)        # -1 = empty slot
        self.k = np.zeros((n,), np.int32)
        self.Ks = np.zeros((n,), np.int32)
        self.eps = np.ones((n,), np.float32)
        self.err = np.zeros((n,), np.float32)
        self.t_submit = np.zeros((n,), np.float64)
        self.t_admit = np.zeros((n,), np.float64)
        self.segments = np.zeros((n,), np.int32)
        self.deadline = np.full((n,), np.inf, np.float64)
        self.attempts = np.zeros((n,), np.int32)
        self.escalated = np.zeros((n,), bool)   # flow-escalation provenance
        self.xs = np.zeros((n,) + shape, dtype)
        self._xs_dev = None     # device mirror of xs, refreshed on admit
        self.z: Any = None                            # device pytree or None
        self.fs: Any = None                           # probe dz rows or None
        self._pending: Optional[_PendingSegment] = None
        self._staged: List[_RetireBatch] = []
        self._staged_flow: List[_FlowBatch] = []
        self.flow_retired_last = 0   # flow terminals in the last finalize
        self._readout_widths: set = set()   # pow2 readout cells traced
        self._probe_fn = None
        self._embed_fn = None
        self._segment_fn = None
        self._readout_fn = None
        self._flow_fn = None

    # ------------------------------------------------------- jit cells ----
    def _cells(self):
        m, integ = self.sched.model, self.sched.model.integ
        ctrl, seg = self.sched.controller, self.sched.seg
        s0 = m.span[0]

        if self._probe_fn is None:
            parametric = m.g_apply is not None

            @jax.jit
            def probe(params, xs, *gps):
                # on a parametric model the correction params ride as a
                # traced operand (gps = (gp,)) — the residual controller
                # consumes g in the probe, so the probe cell must be
                # swap-stable too (no retrace on hot_swap_g)
                ig = bound_integrator(m, gps[0]) if parametric else integ
                z0 = m.embed(params, xs)
                p = ctrl.select(ig, m.field_of(params, xs), z0, m.span)
                return p.K, p.err, z0, p.dz0

            embed = jax.jit(m.embed)

            # the segment cell donates the pool-sized carry buffers
            # (z, fs) — Integrator.segment_cell documents the aliasing
            # contract launch_segment/retire_pending are built around.
            # With a mesh, the carry AND the per-slot conditioning rows
            # shard over the mesh's slot axis and the depth scan stays
            # local per shard; either way this is ONE
            # (shape, seg[, mesh]) jit cell — one fused-kernel trace —
            # across every refill pattern. The model weights lead as a
            # traced (non-donated) operand, and a parametric g appends
            # its params as a trailing one: the params-are-inputs
            # invariant that keeps weights out of the compiled program
            # and makes hot_swap_g free.
            mesh = self.sched.mesh
            donate = self.sched.donate
            g_apply = m.g_apply
            if mesh is None:
                segment = integ.segment_cell(m.field_of, seg, s0=s0,
                                             donate=donate,
                                             g_apply=g_apply)
            else:
                from repro.launch.mesh import sharded_segment_cell
                segment = sharded_segment_cell(
                    integ, m.field_of, seg, mesh=mesh, s0=s0,
                    slot_axis=self.sched.slot_axis, donate=donate,
                    g_apply=g_apply)

            readout = jax.jit(m.readout)

            if m.flow_apply is not None:
                h, fs0 = m.span[1] - m.span[0], m.span[0]

                @jax.jit
                def flow(params, xs, z0, dz0, *fps):
                    # the K=0 tier: one flow-head eval + readout over the
                    # admission probe's already-materialized (z0, dz0);
                    # flow params ride as a traced trailing operand (the
                    # params-are-inputs invariant, same as g). Widths are
                    # pow2-gated by the caller like _readout_finished.
                    return m.readout(params, xs, m.flow_apply(
                        fps[0], h, fs0, z0, dz0))

                self._flow_fn = flow

            self._probe_fn, self._embed_fn = probe, embed
            self._segment_fn, self._readout_fn = segment, readout
        return (self._probe_fn, self._embed_fn, self._segment_fn,
                self._readout_fn)

    # ------------------------------------------------------- occupancy ----
    @property
    def free(self) -> np.ndarray:
        return np.flatnonzero(self.uid < 0)

    @property
    def occupied(self) -> np.ndarray:
        return self.uid >= 0

    def busy(self) -> bool:
        return bool((self.uid >= 0).any())

    # ------------------------------------------------------- admission ----
    def admit(self, reqs: List[Request], submit_t: Dict[int, float],
              now: float, degrade: bool = False) -> Tuple[float, int]:
        """Probe ``reqs`` (padded to pool width: one probe jit cell per
        shape) and scatter them into free slots. Returns (probe cost,
        non-finite probe count). ``degrade`` caps every admission one
        bucket coarser (the overload policy's pressure response)."""
        probe_fn, embed_fn, _, _ = self._cells()
        sched = self.sched
        idx = self.free[:len(reqs)]
        assert len(idx) == len(reqs), "caller admits at most `free` requests"
        n_pad = sched.slots - len(reqs)
        rows = [r.x for r in reqs]
        if sched.fault_injector is not None:
            # chaos hook: poisoned rows feed the probe and the device
            # mirror; self.xs keeps the ORIGINAL input, so a retry of a
            # transiently-poisoned request re-admits clean data
            rows = [sched.fault_injector.corrupt_admission(
                r.uid, r.attempts, x) for r, x in zip(reqs, rows)]
        xs_new = np.stack(rows)
        assert xs_new.dtype == self.xs.dtype, (xs_new.dtype, self.xs.dtype)
        xs_pad = np.concatenate(
            [xs_new, np.repeat(xs_new[:1], n_pad, axis=0)]) \
            if n_pad else xs_new

        fixed = isinstance(sched.controller, FixedController)
        probe_nonfinite = 0
        if fixed:
            z0 = embed_fn(sched.params, jnp.asarray(xs_pad))
            dz0 = None
            Ks_raw = np.full((len(reqs),), sched.controller.K, np.int32)
            errs = np.zeros((len(reqs),), np.float32)
            probe_cost = 0.0
        else:
            Ks_dev, err_dev, z0, dz0 = probe_fn(
                sched.params, jnp.asarray(xs_pad), *sched._g_args())
            Ks_raw = np.asarray(Ks_dev)[:len(reqs)]
            errs = np.asarray(err_dev)[:len(reqs)]
            # the silent k_max clamp in mesh_for_tolerance becomes an
            # observable signal here (one-time warning + TickReport
            # counter); the request itself is the quarantine layer's job
            probe_nonfinite = screen_probe_errors(errs)
            # the probe is padded to pool width, so the oracle prices a
            # pool-width program regardless of how many rows refilled
            probe_cost = sched.oracle.probe_cost(
                self.shape, sched.slots,
                getattr(sched.controller, "probe_nfe", 0))
        Ks = snap_to_buckets(Ks_raw, sched.ecfg.buckets)
        if degrade:
            # graceful degradation: serve one bucket coarser than asked
            # while the queue is over pressure — agreement trades off
            # measurably, nothing is refused
            b = np.asarray(sorted(sched.ecfg.buckets), np.int32)
            Ks = b[np.maximum(np.searchsorted(b, Ks) - 1, 0)]
        # retry-ladder escalation: a re-queued request never re-serves
        # below its K_floor (the next-finer bucket than the failed one)
        floors = np.asarray([r.K_floor for r in reqs], np.int32)
        Ks = np.maximum(Ks, floors)

        # K=0 flow tier (core/flowhead.py): probe-easy rows never touch
        # a slot — one flow-head eval off the probe's (z0, dz0), staged
        # async and materialized in finalize_retired. The remaining rows
        # (and the padded probe outputs) are subset so every line below
        # runs exactly as if only they had been admitted; with the tier
        # disabled (router is None) this block never executes and
        # admission is bitwise identical to pre-flow.
        if sched.router is not None and not fixed:
            flow_sel = np.asarray(sched.router.flow_mask(
                errs, sched.ecfg.tol, floors))
            if flow_sel.any():
                flow_cost = sched.oracle.flow_cost(
                    self.shape, int(flow_sel.sum()))
                sched._flow_cost_tick += flow_cost
                self._stage_flow(reqs, flow_sel, xs_new, z0, dz0, errs,
                                 submit_t, now,
                                 t_done=now + probe_cost + flow_cost)
                keep = np.flatnonzero(~flow_sel)
                reqs = [reqs[i] for i in keep]
                xs_new = xs_new[keep]
                Ks, errs = Ks[keep], errs[keep]
                idx = idx[:len(reqs)]
                if not len(reqs):
                    return probe_cost, probe_nonfinite
                # remap the PADDED probe outputs so rows 0..len(reqs)-1
                # are the kept rows (take_rows and the first-admission
                # full-pool shortcut below both rely on that layout)
                pad_pos = jnp.asarray(np.concatenate(
                    [keep, np.full(sched.slots - len(keep), keep[0])]))
                remap = lambda t: jax.tree_util.tree_map(
                    lambda l: l[pad_pos], t)
                z0 = remap(z0)
                dz0 = None if dz0 is None else remap(dz0)

        # scatter: host rows directly, device pytrees leaf-wise. On the
        # pool's first admission the padded probe output IS the pool state.
        jidx = jnp.asarray(idx)
        take_rows = lambda t: jax.tree_util.tree_map(
            lambda l: l[:len(reqs)], t)
        if self.z is None:
            scatter = lambda _, new: jax.tree_util.tree_map(
                lambda l: jnp.asarray(l), new)
            self.z = scatter(None, z0)
            self.fs = None if dz0 is None else scatter(None, dz0)
        else:
            upd = lambda old, new: jax.tree_util.tree_map(
                lambda o, nl: o.at[jidx].set(nl), old, take_rows(new))
            self.z = upd(self.z, z0)
            if self.fs is not None:
                self.fs = upd(self.fs, dz0)
        span = sched.model.span
        for j, i in enumerate(idx):
            r = reqs[j]
            self.uid[i] = r.uid
            self.k[i] = 0
            self.Ks[i] = int(Ks[j])
            self.eps[i] = (span[1] - span[0]) / float(Ks[j])
            self.err[i] = float(errs[j])
            self.t_submit[i] = submit_t.pop(r.uid)
            self.t_admit[i] = now
            self.segments[i] = 0
            self.deadline[i] = np.inf if r.deadline is None else r.deadline
            self.attempts[i] = r.attempts
            self.escalated[i] = r.escalated
            self.xs[i] = r.x
        # device mirror of xs: scatter only the refilled rows (a full
        # re-upload per admission would put the big operand back on the
        # host->device path every tick under steady streaming traffic)
        if self._xs_dev is None:
            self._xs_dev = jnp.asarray(self.xs)
        else:
            self._xs_dev = self._xs_dev.at[jidx].set(jnp.asarray(xs_new))
        return probe_cost, probe_nonfinite

    def _stage_flow(self, reqs: List[Request], flow_sel: np.ndarray,
                    xs_new: np.ndarray, z0, dz0, errs: np.ndarray,
                    submit_t: Dict[int, float], now: float,
                    t_done: float) -> None:
        """Dispatch the flow-tier rows' K=0 eval (async device future,
        pow2-padded gather like ``_readout_finished``) and stage the
        batch for ``finalize_retired``. Rows are gathered from the
        PADDED probe outputs, so this is purely a read of state the
        probe already materialized — no extra probe, no slot."""
        sched = self.sched
        fidx = np.flatnonzero(flow_sel)
        w = min(1 << (len(fidx) - 1).bit_length(), sched.slots)
        pad = fidx if w == len(fidx) else np.concatenate(
            [fidx, np.repeat(fidx[:1], w - len(fidx))])
        jf = jnp.asarray(pad)
        gather = lambda t: jax.tree_util.tree_map(lambda l: l[jf], t)
        outs = self._flow_fn(sched.params, jnp.asarray(xs_new[pad]),
                             gather(z0), gather(dz0), *sched._flow_args())
        rs = [reqs[i] for i in fidx]
        self._staged_flow.append(_FlowBatch(
            n=len(fidx), outs=outs, t_done=t_done,
            uid=np.asarray([r.uid for r in rs], np.int64),
            err=errs[fidx].copy(),
            t_submit=np.asarray([submit_t.pop(r.uid) for r in rs],
                                np.float64),
            t_admit=now,
            deadline=np.asarray(
                [np.inf if r.deadline is None else r.deadline
                 for r in rs], np.float64),
            attempts=np.asarray([r.attempts for r in rs], np.int32),
            xs=np.stack([r.x for r in rs])))

    # --------------------------------------------------------- segment ----
    def launch_segment(self, t_done: float) -> None:
        """Dispatch one ``seg``-step advance of the pool WITHOUT reading
        anything back: JAX async dispatch returns futures immediately,
        so the device chews on the segment while the host does whatever
        comes next. The donated carry buffers (z, fs) are consumed by
        the call — the returned futures become the pool's next resident
        buffers, and any read of the OLD state (readout gathers, refill
        scatters) must already be enqueued, which the retire -> admit ->
        launch tick order guarantees. The one blocking transfer (the
        stacked retire meta) is deferred to ``retire_pending``."""
        _, _, segment_fn, _ = self._cells()
        assert self._pending is None, "one in-flight segment per pool"
        assert self._xs_dev is not None  # a busy pool has admitted
        k_old = self.k.copy()
        occ = self.occupied.copy()
        z, fs, meta = segment_fn(
            self.sched.params, self._xs_dev, self.z, jnp.asarray(self.k),
            jnp.asarray(self.Ks), jnp.asarray(self.eps), self.fs,
            *self.sched._g_args())
        self.z, self.fs = z, fs
        self._pending = _PendingSegment(meta=meta, k_old=k_old, occ=occ,
                                        t_done=t_done)

    def retire_pending(self) -> _RetireStats:
        """Block on the pending segment's stacked ``[k'; finished;
        nonfinite]`` meta — still ONE batched device->host transfer per
        segment — stage terminal rows for retirement (gated readout
        enqueued async), requeue retryable failures, and free their
        slots. Returns per-pool ``_RetireStats``; the staged completions
        materialize later in ``finalize_retired``.

        Precedence: quarantine beats finished (a non-finite row's
        finished flag is meaningless — NaN froze or compared its way
        past Ks), finished beats deadline (a request that FINISHED by
        the time the segment retired completes ``ok`` even if its stamp
        lands past the deadline — eviction is only for rows that would
        keep burning segments they can no longer use)."""
        p = self._pending
        assert p is not None, "retire_pending without a pending segment"
        self._pending = None
        sched = self.sched
        meta = np.array(p.meta)   # the one blocking transfer per segment
        self.k = meta[0]
        occ = p.occ
        self.segments[occ] += 1
        useful = int((self.k - p.k_old)[occ].sum())
        fin_row = meta[1] != 0
        if sched.fault_injector is not None:
            # chaos hook: lose completion signals. Keyed per (uid,
            # segment count), so a dropped flag is re-drawn next segment
            # and the request still terminates — zero-hang for p < 1.
            fin_row = sched.fault_injector.drop_retire_flags(
                self.uid, self.segments, fin_row)
        nonfin = occ & (meta[2] != 0)
        finished = occ & fin_row & ~nonfin
        expired = occ & ~nonfin & ~finished & (self.deadline < p.t_done)

        if sched.ledger is not None:
            # residual-ledger capture (launch/refinery.py): interior,
            # healthy rows only — quarantined and deadline-evicted rows
            # are excluded (the STATUSES gate), finished rows sit at the
            # span end where no further step starts. ONE extra readout
            # per retire, rate-gated inside the ledger, never priced by
            # the cost oracle, and purely a READ of the resident state
            # (enqueued before the next donating launch) — so capture
            # on/off completions stay uid-for-uid bitwise identical.
            live = occ & ~nonfin & ~fin_row & ~expired \
                & (self.k < self.Ks)
            sched.ledger.capture_pool(self, np.flatnonzero(live))

        idx: List[int] = [int(i) for i in np.flatnonzero(finished)]
        status = ["ok" if self.attempts[i] == 0 else
                  ("escalated" if self.escalated[i] else "retried")
                  for i in idx]
        requeued = 0
        for i in np.flatnonzero(nonfin | expired):
            st = "diverged" if nonfin[i] else "deadline"
            # escalate one bucket finer; at the top bucket (where a
            # poisoned PROBE lands every corrupted request, since
            # mesh_for_tolerance clamps non-finite k to k_max) retry at
            # the same bucket — a transient fault deserves one clean
            # re-run, still bounded by the RetryPolicy
            nxt = next_bucket_above(int(self.Ks[i]), sched.ecfg.buckets) \
                or int(self.Ks[i])
            if sched.retry.should_retry(st, int(self.attempts[i])):
                self._requeue_slot(int(i), nxt)
                requeued += 1
            else:
                idx.append(int(i))
                status.append(st)
        retired = 0
        if idx:
            retired = self._stage_retire(np.asarray(idx, np.int64),
                                         p.t_done, status)
        return _RetireStats(
            retired=retired, useful=useful, occupied=int(occ.sum()),
            quarantined=int(nonfin.sum()),
            deadline_evicted=int(expired.sum()), requeued=requeued)

    def _requeue_slot(self, i: int, K_floor: int) -> None:
        """Send slot ``i`` back through the retry ladder: the request
        re-enters the FRONT of the queue (so both tick variants admit it
        at the very next ``_admit_tick`` — the sync/overlap parity
        contract) with its K_floor escalated one bucket, and the failed
        attempt's work charged to the scheduler's ``_nfe_extra`` ledger.
        The slot frees without a readout — nothing terminal happened."""
        sched = self.sched
        uid = int(self.uid[i])
        sched._nfe_extra[uid] = sched._nfe_extra.get(uid, 0) \
            + sched.probe_nfe + sched.stages * int(self.k[i])
        sched._submit_t[uid] = float(self.t_submit[i])
        deadline = float(self.deadline[i])
        sched._queue.appendleft(Request(
            uid=uid, x=self.xs[i].copy(),
            deadline=deadline if np.isfinite(deadline) else None,
            attempts=int(self.attempts[i]) + 1, K_floor=K_floor,
            escalated=bool(self.escalated[i])))
        self.uid[i] = -1
        self.Ks[i] = 0
        self.eps[i] = 1.0
        self.k[i] = 0
        self.deadline[i] = np.inf

    def _stage_retire(self, idx: np.ndarray, t_done: float,
                      status: List[str]) -> int:
        """Retire the slots ``idx``: enqueue the rows' readout (async;
        force-retired rows get the same gated readout — their partial
        state IS the best-effort answer), snapshot their host rows, and
        mark them refillable."""
        outs = self._readout_finished(idx)
        self._staged.append(_RetireBatch(
            idx=idx, outs=outs, t_done=t_done,
            fused=self.sched.model.integ.fused_available(z=self.z),
            uid=self.uid[idx].copy(), K=self.Ks[idx].copy(),
            k_done=self.k[idx].copy(),
            err=self.err[idx].copy(), t_submit=self.t_submit[idx].copy(),
            t_admit=self.t_admit[idx].copy(),
            segments=self.segments[idx].copy(), status=list(status)))
        self.uid[idx] = -1            # retire: slot becomes refillable
        self.Ks[idx] = 0              # Ks==0 keeps the row frozen
        self.eps[idx] = 1.0
        self.k[idx] = 0
        self.deadline[idx] = np.inf
        return len(idx)

    def _readout_finished(self, idx: np.ndarray):
        """Readout of ONLY the finished rows (it used to recompute the
        whole pool — including empty ``Ks == 0`` rows — whenever any
        single slot finished). Gather widths are padded to the next
        power of two, capped at the pool width, so the readout jit cells
        are ``(shape, width <= slots)``: a lone finishing slot pays a
        width-1 readout, and the cell count stays log2(slots). Returns
        the device future — materialization is ``finalize_retired``'s
        job."""
        _, _, _, readout_fn = self._cells()
        w = min(1 << (len(idx) - 1).bit_length(), self.sched.slots)
        pad = idx if w == len(idx) else np.concatenate(
            [idx, np.repeat(idx[:1], w - len(idx))])
        self._readout_widths.add(int(w))
        jidx = jnp.asarray(pad)
        z_rows = jax.tree_util.tree_map(lambda l: l[jidx], self.z)
        return readout_fn(self.sched.params, self._xs_dev[jidx], z_rows)

    def finalize_retired(self) -> List[InflightCompleted]:
        """Materialize staged completions — the only place readout rows
        cross to the host. The overlap loop calls this AFTER dispatching
        the next segments, so the transfer rides behind device work; the
        sync loop calls it immediately."""
        sched = self.sched
        done: List[InflightCompleted] = []
        self.flow_retired_last = 0
        for fb in self._staged_flow:
            outs = np.asarray(fb.outs)
            for j in range(fb.n):
                uid = int(fb.uid[j])
                attempts = int(fb.attempts[j])
                row = outs[j]
                if sched.fault_injector is not None:
                    # chaos hook: a poisoned FLOW eval (the only fault
                    # that can reach this tier — admission-poisoned
                    # inputs fail the probe's finite screen and are
                    # never flow-routed)
                    row = sched.fault_injector.corrupt_flow_eval(
                        uid, attempts, row)
                if np.isfinite(row).all():
                    # flow_mask bars K_floor > 0, so attempts == 0 here
                    self.flow_retired_last += 1
                    sched._flow_tick += 1
                    sched.total_flow_served += 1
                    done.append(InflightCompleted(
                        uid=uid, outputs=row, K=0,
                        nfe=sched.nfe_flow + sched._nfe_extra.pop(uid, 0),
                        err_probe=float(fb.err[j]), fused_kernel=False,
                        t_submit=float(fb.t_submit[j]),
                        t_admit=fb.t_admit, t_done=fb.t_done,
                        segments=0, status="ok"))
                    continue
                if sched.retry.should_retry("diverged", attempts):
                    # escalation: bill the flow attempt's nfe, requeue
                    # into the K-bucket ladder at the coarsest bucket
                    # (the front of the queue, like _requeue_slot — the
                    # sync/overlap parity contract); K_floor > 0 also
                    # bars re-routing to flow
                    sched._nfe_extra[uid] = \
                        sched._nfe_extra.get(uid, 0) + sched.nfe_flow
                    sched._submit_t[uid] = float(fb.t_submit[j])
                    dl = float(fb.deadline[j])
                    sched._queue.appendleft(Request(
                        uid=uid, x=fb.xs[j].copy(),
                        deadline=dl if np.isfinite(dl) else None,
                        attempts=attempts + 1,
                        K_floor=min(sched.ecfg.buckets),
                        escalated=True))
                    sched._esc_tick += 1
                    sched.total_escalated += 1
                    continue
                self.flow_retired_last += 1
                done.append(InflightCompleted(
                    uid=uid, outputs=row, K=0,
                    nfe=sched.nfe_flow + sched._nfe_extra.pop(uid, 0),
                    err_probe=float(fb.err[j]), fused_kernel=False,
                    t_submit=float(fb.t_submit[j]), t_admit=fb.t_admit,
                    t_done=fb.t_done, segments=0, status="diverged"))
        self._staged_flow = []
        for b in self._staged:
            outs = np.asarray(b.outs)
            for j in range(len(b.idx)):
                uid = int(b.uid[j])
                # nfe bills the depth steps actually TAKEN (k_done == K
                # for ok rows, fewer for evictions) plus every failed
                # attempt's probe + steps from the _nfe_extra ledger
                done.append(InflightCompleted(
                    uid=uid, outputs=outs[j], K=int(b.K[j]),
                    nfe=sched.probe_nfe + sched.stages * int(b.k_done[j])
                    + sched._nfe_extra.pop(uid, 0),
                    err_probe=float(b.err[j]), fused_kernel=b.fused,
                    t_submit=float(b.t_submit[j]),
                    t_admit=float(b.t_admit[j]), t_done=b.t_done,
                    segments=int(b.segments[j]), status=b.status[j]))
        self._staged = []
        return done

    def run_segment(self, now_done: float) -> Tuple[List[InflightCompleted],
                                                    _RetireStats]:
        """The SYNCHRONOUS segment: one ``seg``-step advance of the whole
        pool, finished slots retired before returning. Exactly
        ``launch_segment`` + ``retire_pending`` + ``finalize_retired``
        with zero lag — the overlap loop runs the same three phases one
        segment apart, which is why its completions are uid-for-uid
        identical to this path (pinned in tests/test_scheduler.py).
        Returns (completions, per-pool retire stats)."""
        self.launch_segment(now_done)
        stats = self.retire_pending()
        return self.finalize_retired(), stats


class InflightScheduler:
    """Continuous-batching serving loop: submit as traffic arrives, call
    ``step()`` repeatedly; each step admits into free slots and advances
    every busy pool by one segment. See the module docstring for the
    slot/segment model and the virtual-cost clock.

    ``mesh`` grows the pool past one chip: ``slots`` is the GLOBAL pool
    width, sharded row-wise over the mesh's ``slot_axis`` (per-device
    sub-pools of ``slots / axis_size`` rows) while admission stays one
    global FIFO queue. Between segments, retire/refill operates on the
    gathered per-slot ``k``/``Ks`` rows exactly as on one device — slot
    state is data, so the host never needs to know which device holds
    which slot — and the probe path is unchanged (one pool-width probe
    cell). The model weights are placed once, replicated on every device
    of the mesh, so no segment re-sends them. ``slots`` must be a
    multiple of the axis size; checked here with a remedy-naming error.

    ``overlap=True`` swaps the synchronous tick for the pipelined one
    (serve.py ``--overlap``): segment N+1 is dispatched while segment
    N's retire metadata is still in flight, so host-side bookkeeping
    overlaps device compute (see ``_step_overlap``). Completions,
    virtual-clock stamps, and ledger totals are identical to the
    synchronous loop — the sync path is kept as the oracle the overlap
    path is pinned against."""

    def __init__(self, model: DepthModel,
                 engine_cfg: Optional[EngineConfig] = None,
                 *, slots: int = 4, seg: int = 2, mesh=None,
                 slot_axis: str = "data",
                 oracle: Optional[CostOracle] = None,
                 overlap: bool = False,
                 donate: Optional[bool] = None,
                 queue_cap: Optional[int] = None,
                 overload_policy: str = "shed",
                 deadline: Optional[float] = None,
                 retry: Optional[RetryPolicy] = None,
                 fault_injector: Optional[FaultInjector] = None,
                 ledger=None):
        engine_cfg = engine_cfg or EngineConfig()
        if overload_policy not in ("shed", "degrade", "block"):
            raise ValueError(
                f"overload_policy={overload_policy!r}: expected 'shed' "
                "(refuse with status='shed'), 'degrade' (admit one "
                "bucket coarser under pressure), or 'block' (raise "
                "QueueFull; caller backs off)")
        if queue_cap is not None and queue_cap < 1:
            raise ValueError(f"queue_cap must be >= 1, got {queue_cap} "
                             "(a zero-width queue can never admit)")
        model = prepare_model(model, engine_cfg)
        if seg < 1:
            raise ValueError(f"seg must be >= 1, got {seg}")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if mesh is not None:
            n = mesh.shape[slot_axis]
            if slots % n:
                raise ValueError(
                    f"slots={slots} does not divide the '{slot_axis}' "
                    f"mesh axis ({n}); the pool shards row-wise — size "
                    "slots as a multiple of the axis (e.g. "
                    f"slots={n * max(1, slots // n)})")
        self.mesh = mesh
        self.slot_axis = slot_axis
        self.model = model
        # model weights: one resident copy (replicated over the mesh when
        # there is one), a traced operand of every pool cell
        self.params = jax.tree_util.tree_map(jnp.asarray, model.params)
        if mesh is not None:
            self.params = jax.device_put(
                self.params, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()))
        self.ecfg = engine_cfg
        self.slots = int(slots)
        self.seg = int(seg)
        # controller policy decides off the BOUND integrator (a
        # parametric g counts as a correction for controller="auto");
        # the pool cells re-bind g over the traced gp operand themselves
        self.controller = make_controller(bound_integrator(model),
                                          engine_cfg)
        # hot-swappable correction params: host-held, appended to every
        # parametric probe/segment cell call — hot_swap_g replaces them
        # between segments with zero retraces and no pool drain
        self.g_params = None if model.g_apply is None else \
            jax.tree_util.tree_map(jnp.asarray, model.g_params)
        # K=0 flow tier (core/flowhead.py): hot-swappable like g, routed
        # by the TierRouter off the admission probe's difficulty estimate
        self.flow_params = None if model.flow_apply is None else \
            jax.tree_util.tree_map(jnp.asarray, model.flow_params)
        self.router = TierRouter(flow_threshold=engine_cfg.flow_threshold) \
            if engine_cfg.flow_threshold > 0 else None
        self.ledger = ledger   # optional ResidualLedger (launch/refinery)
        self.overlap = bool(overlap)
        # Donating the carry buffers halves pool memory on accelerators,
        # where XLA aliases them in place without giving up async
        # dispatch. The auto default keeps donation off on the CPU
        # backend, where the pool lives in host memory and aliasing
        # saves nothing that bounds a run; pass donate=True to force it
        # (the aliasing contract itself compiles and verifies on every
        # backend — tests/test_scheduler.py).
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self.donate = bool(donate)
        self.oracle: CostOracle = oracle or SequentialEvalOracle()
        self.stages = model.integ.tableau.stages
        self.now = 0.0
        self.ticks = 0
        self.dispatches = 0
        self.total_cost = 0.0
        self.total_probe_cost = 0.0
        self.total_useful_steps = 0
        self.total_slot_steps = 0
        self.total_occupied_steps = 0
        # cumulative hardening counters (per-tick twins live in
        # TickReport): what the serve CLI's live progress line reports
        self.total_quarantined = 0
        self.total_deadline_evicted = 0
        self.total_requeued = 0
        self.total_shed = 0
        self.total_flow_served = 0
        self.total_escalated = 0
        # per-tick flow accounting, accrued inside pool.admit/finalize
        # (reset at the top of each tick variant)
        self._flow_tick = 0
        self._esc_tick = 0
        self._flow_cost_tick = 0.0
        self.last_report = TickReport()
        self.queue_cap = None if queue_cap is None else int(queue_cap)
        self.overload_policy = overload_policy
        self.default_deadline = deadline  # relative slack, applied at submit
        self.retry = retry or RetryPolicy()
        self.fault_injector = fault_injector
        self._queue: deque = deque()
        self._submit_t: Dict[int, float] = {}
        self._uid = 0
        self._pools: Dict[Tuple, _SlotPool] = {}
        self._shed: List[InflightCompleted] = []   # terminal, pre-admission
        self._nfe_extra: Dict[int, int] = {}       # failed attempts' work

    # ----------------------------------------------------------- queue ----
    @property
    def probe_nfe(self) -> int:
        """Per-request probe cost net of the reused first stage (same
        accounting as MultiRateEngine.probe_nfe)."""
        return probe_net_nfe(self.controller)

    @property
    def nfe_flow(self) -> int:
        """NFE billed to a flow-tier completion: the raw probe evals
        plus ZERO solver steps. ``probe_nfe`` nets out the reused first
        stage, but on the flow tier that stage is consumed by the flow
        combine's ``eps*dz`` term rather than a solver, so it is billed
        back (+1). Same accounting as MultiRateEngine.nfe_flow."""
        return self.probe_nfe + 1

    def _flow_args(self) -> Tuple:
        """Trailing flow-cell operands, the flow twin of ``_g_args``."""
        return () if self.model.flow_apply is None else (self.flow_params,)

    def _g_args(self) -> Tuple:
        """Trailing cell operands for the hot-swappable correction:
        ``(g_params,)`` on a parametric model, ``()`` otherwise. Read at
        CALL time, so a hot_swap_g is visible from the very next
        launched segment."""
        return () if self.model.g_apply is None else (self.g_params,)

    def hot_swap_g(self, gp):
        """Install new correction params BETWEEN segments: the pool
        cells take them as traced inputs (same treedef/shapes/dtypes
        enforced by ``validate_g_swap``), so the swap compiles nothing,
        drains nothing, and every segment launched after this call —
        including refills of slots admitted under the old params —
        integrates with the new g. Under ``overlap=True`` the one
        in-flight segment finishes on the old params (it was dispatched
        with them); the swap is visible from the next launch. Returns
        the previous params — the refinery's rollback handle."""
        if self.model.g_apply is None:
            raise ValueError(
                "hot_swap_g on a non-parametric model: build the "
                "DepthModel with g_apply/g_params (params-are-inputs) "
                "to make the correction swappable")
        gp = jax.tree_util.tree_map(jnp.asarray, gp)
        validate_g_swap(self.g_params, gp)
        old, self.g_params = self.g_params, gp
        return old

    def hot_swap_flow(self, fp):
        """Install new flow-head params between ticks — identical
        contract to ``hot_swap_g`` (zero retraces, no drain; the params
        are traced operands read at flow-cell CALL time). Returns the
        previous params as the rollback handle."""
        if self.model.flow_apply is None:
            raise ValueError(
                "hot_swap_flow on a model without a flow head: build "
                "the DepthModel with flow_apply/flow_params to make the "
                "K=0 tier swappable")
        fp = jax.tree_util.tree_map(jnp.asarray, fp)
        validate_g_swap(self.flow_params, fp, label="hot_swap_flow")
        old, self.flow_params = self.flow_params, fp
        return old

    def can_submit(self) -> bool:
        """False exactly when the next ``submit`` would raise QueueFull:
        the bounded queue is at cap under ``overload_policy='block'``.
        (``shed`` always accepts — and may refuse terminally; ``degrade``
        always admits, one bucket coarser under pressure.)"""
        return not (self.queue_cap is not None
                    and self.overload_policy == "block"
                    and len(self._queue) >= self.queue_cap)

    def submit(self, x, t: Optional[float] = None,
               deadline: Optional[float] = None) -> int:
        """Queue a request. ``t`` is its arrival time on the virtual
        clock, defaulting to now; a past ``t`` records the true arrival
        of a request the caller is admitting late (the replay driver's
        normal case — queue wait starts at ``t``). A FUTURE ``t`` is
        only meaningful when the scheduler is idle, where the clock
        idle-jumps forward to it; with work pending it is refused,
        because jumping the clock mid-flight would bill every in-flight
        request for time no segment ran — ``step()`` until ``now >= t``
        instead (as ``launch/workload.py::replay_scheduler`` does).

        ``deadline`` is ABSOLUTE on the virtual clock (defaulting to
        ``t + self.default_deadline`` when the scheduler has a default
        slack); a request past its deadline is dropped from the queue or
        evicted from its slot with ``status="deadline"``. Over a full
        bounded queue: ``shed`` returns a uid whose terminal
        ``status="shed"`` record surfaces from the next ``step()``;
        ``block`` raises ``QueueFull`` (probe with ``can_submit``)."""
        t = self.now if t is None else float(t)
        if t > self.now:
            if self.pending:
                raise ValueError(
                    f"submit at t={t} > now={self.now} with "
                    f"{self.pending} requests pending: advancing the "
                    "clock mid-flight would misattribute latency; "
                    "step() until now >= t, then submit")
            self.advance_to(t)
        if deadline is None and self.default_deadline is not None:
            deadline = t + float(self.default_deadline)
        at_cap = self.queue_cap is not None \
            and len(self._queue) >= self.queue_cap
        if at_cap and self.overload_policy == "block":
            raise QueueFull(
                f"admission queue at cap ({self.queue_cap}) under "
                "overload_policy='block'; back off and resubmit "
                "(can_submit() is the non-raising probe)")
        self._uid += 1
        if at_cap and self.overload_policy == "shed":
            # terminal refusal: no slot, no probe, no outputs — the
            # record surfaces from the next step() like any completion
            self._shed.append(InflightCompleted(
                uid=self._uid, outputs=None, K=0, nfe=0, err_probe=0.0,
                fused_kernel=False, t_submit=t, t_admit=t, t_done=t,
                segments=0, status="shed"))
            return self._uid
        self._queue.append(Request(uid=self._uid, x=np.asarray(x),
                                   deadline=deadline))
        self._submit_t[self._uid] = t
        return self._uid

    def advance_to(self, t: float) -> None:
        """Idle-jump the virtual clock forward (never backward). Refused
        while work is pending, for the same reason ``submit`` refuses a
        future ``t`` then: the jump would bill every in-flight request
        for time no segment ran."""
        if float(t) > self.now and self.pending:
            raise ValueError(
                f"advance_to(t={t}) > now={self.now} with {self.pending} "
                "requests pending: the clock only idle-jumps; step() "
                "until now >= t instead")
        self.now = max(self.now, float(t))

    @property
    def pending(self) -> int:
        """Requests not yet surfaced: queued + in flight + terminal
        records (shed refusals) awaiting the next ``step()``."""
        inflight = sum(int(p.occupied.sum()) for p in self._pools.values())
        return len(self._queue) + inflight + len(self._shed)

    def __len__(self) -> int:
        return len(self._queue)

    # ------------------------------------------------------------ tick ----
    def step(self) -> List[InflightCompleted]:
        """One scheduling round. The synchronous tick (default) admits,
        advances every busy pool by one segment, and retires — blocking
        on each pool's result before moving on. ``overlap=True`` runs
        the pipelined tick instead: retire the PREVIOUS tick's segments,
        admit into the freed slots, dispatch the next segments, and only
        then materialize outputs — so host bookkeeping overlaps device
        compute. Both paths admit identical request->slot assignments
        and stamp identical virtual-clock times; only wall-clock
        behavior differs."""
        return self._step_overlap() if self.overlap else self._step_sync()

    def _admit_tick(self) -> Tuple[float, int, Dict[Tuple, float],
                                   List[InflightCompleted], int]:
        """Refill free slots from the FIFO queue (probe-on-admission).
        Shared verbatim by the sync and overlap ticks, so the two loops
        admit identical request->slot assignments tick for tick — the
        root of the uid-for-uid parity contract. Requests already past
        their deadline drop here, terminal, without costing a probe.
        Returns (probe_cost, admitted, per-pool probe cost, dropped
        terminal records, non-finite probe count)."""
        probe_cost = 0.0
        admitted = 0
        probe_nonfinite = 0
        pool_probe: Dict[Tuple, float] = {}
        dropped: List[InflightCompleted] = []
        # degrade pressure is measured once at tick start, so every
        # admission this tick sees the same policy decision
        degrade = (self.overload_policy == "degrade"
                   and self.queue_cap is not None
                   and len(self._queue) > self.queue_cap)
        # -- admission: FIFO per (shape, dtype) pool; a full pool does not
        #    block other pools' admissions (head-of-line blocking stays
        #    within a cell).
        if self._queue:
            batches: Dict[Tuple, List[Request]] = {}
            budget: Dict[Tuple, int] = {}
            leftover: deque = deque()
            while self._queue:
                r = self._queue.popleft()
                if r.deadline is not None and r.deadline < self.now:
                    # expired while queued: terminal, no slot ever held.
                    # nfe surfaces any failed-attempt work (a retry that
                    # expired waiting for its re-admission).
                    dropped.append(InflightCompleted(
                        uid=r.uid, outputs=None, K=0,
                        nfe=self._nfe_extra.pop(r.uid, 0), err_probe=0.0,
                        fused_kernel=False,
                        t_submit=self._submit_t.pop(r.uid),
                        t_admit=self.now, t_done=self.now,
                        segments=0, status="deadline"))
                    continue
                # pools key on (shape, dtype): same-shape requests of a
                # different dtype must not silently cast into a pool's
                # storage (the jit-cell retrace boundary, made explicit)
                key = (r.x.shape, r.x.dtype.str)
                if key not in self._pools:
                    self._pools[key] = _SlotPool(self, r.x.shape,
                                                 r.x.dtype)
                if key not in budget:
                    budget[key] = len(self._pools[key].free)
                if budget[key] > 0:
                    budget[key] -= 1
                    batches.setdefault(key, []).append(r)
                else:
                    leftover.append(r)
            self._queue = leftover
            for key, batch in batches.items():
                # every pool's probe starts at tick start (concurrent
                # cells) — t_admit no longer absorbs other pools' probes
                pc, n_bad = self._pools[key].admit(
                    batch, self._submit_t, self.now, degrade=degrade)
                pool_probe[key] = pc
                probe_cost += pc
                probe_nonfinite += n_bad
                admitted += len(batch)
        return probe_cost, admitted, pool_probe, dropped, probe_nonfinite

    def _finish_tick(self, *, cost, probe_cost, admitted, retired,
                     useful, total, occupied, quarantined=0,
                     deadline_evicted=0, requeued=0, shed=0,
                     probe_nonfinite=0, flow_served=0,
                     escalated=0) -> None:
        """Advance the virtual clock and the resource ledgers — the one
        accounting epilogue both tick variants share."""
        self.now += cost
        self.ticks += 1
        self.total_cost += cost
        self.total_probe_cost += probe_cost
        self.total_useful_steps += useful
        self.total_slot_steps += total
        self.total_occupied_steps += occupied
        self.total_quarantined += quarantined
        self.total_deadline_evicted += deadline_evicted
        self.total_requeued += requeued
        self.total_shed += shed
        self.last_report = TickReport(
            cost=cost, probe_cost=probe_cost, admitted=admitted,
            retired=retired, useful_steps=useful, total_steps=total,
            occupied_steps=occupied, quarantined=quarantined,
            deadline_evicted=deadline_evicted, requeued=requeued,
            shed=shed, probe_nonfinite=probe_nonfinite,
            flow_served=flow_served, escalated=escalated)

    def _step_sync(self) -> List[InflightCompleted]:
        """The synchronous tick: (1) refill free slots from the queue
        (probe-on-admission), (2) advance every busy pool by one segment,
        (3) retire finished slots. Advances the virtual clock by the
        tick's summed cost (the resource ledger); completions are stamped
        at end-of-tick with only THEIR pool's probe + segment cost —
        pools are concurrent cells, so per-request latency must not
        depend on ``(shape, dtype)`` key insertion order (it used to:
        the pre-oracle clock accumulated segment cost across pools in
        dict-iteration order, billing later-iterated pools for every
        earlier pool's segment; pinned in tests/test_scheduler.py)."""
        done: List[InflightCompleted] = list(self._shed)
        shed = len(done)
        self._shed = []
        self._flow_tick = self._esc_tick = 0
        self._flow_cost_tick = 0.0
        probe_cost, admitted, pool_probe, dropped, probe_nonfinite = \
            self._admit_tick()
        done.extend(dropped)
        cost = probe_cost + self._flow_cost_tick
        # -- segments
        useful = total = occupied = retired = 0
        quarantined = evicted = requeued = 0
        for key, pool in self._pools.items():
            if not pool.busy():
                continue
            seg_cost = self.oracle.segment_cost(pool.shape, self.seg,
                                                self.slots, self.stages)
            if self.fault_injector is not None:
                # virtual straggler: keyed on the DISPATCH sequence, not
                # the tick counter — the overlap loop burns a retire-only
                # flush tick whenever the pool drains, so tick counters
                # drift across loops while the dispatch sequence stays
                # identical (and with it the fault schedule)
                seg_cost = self.fault_injector.inflate_segment_cost(
                    self.dispatches, seg_cost)
            self.dispatches += 1
            cost += seg_cost
            d, st = pool.run_segment(
                self.now + pool_probe.get(key, 0.0) + seg_cost)
            done.extend(d)
            retired += len(d)
            useful += st.useful
            total += self.slots * self.seg
            occupied += st.occupied * self.seg
            quarantined += st.quarantined
            evicted += st.deadline_evicted
            requeued += st.requeued
        # flow-only admissions leave their pool non-busy (flow rows
        # never occupy slots), so run_segment never fires for them —
        # drain any pool still holding staged flow batches here or the
        # tick would silently strand (and hang) those requests
        for pool in self._pools.values():
            if pool._staged_flow:
                d = pool.finalize_retired()
                done.extend(d)
                retired += len(d)
        self._finish_tick(cost=cost, probe_cost=probe_cost,
                          admitted=admitted,
                          retired=retired + shed + len(dropped),
                          useful=useful, total=total, occupied=occupied,
                          quarantined=quarantined,
                          deadline_evicted=evicted + len(dropped),
                          requeued=requeued, shed=shed,
                          probe_nonfinite=probe_nonfinite,
                          flow_served=self._flow_tick,
                          escalated=self._esc_tick)
        return done

    def _step_overlap(self) -> List[InflightCompleted]:
        """The pipelined tick: launch segment N+1 with a one-segment-
        lagged retire, so the device never idles through host
        bookkeeping and the host never idles through a segment. Order:

          1. **retire** every pool's PENDING segment (launched last
             tick): block on its stacked ``[k'; finished]`` meta — by
             now the device has had a full host-phase head start on it —
             stage finished rows (readout gather enqueued async), free
             their slots;
          2. **admit** into the freed slots (``_admit_tick``, shared
             with the sync path — identical request->slot assignments);
          3. **launch** the next segment of every busy pool — async
             dispatch returns immediately, the donated carry buffers
             swap roles (in-flight vs resident), and every line of host
             work after this point overlaps device compute;
          4. **materialize** the staged completions — even the readout
             device->host transfer rides behind the just-dispatched
             segments.

        Per-tick attribution differs from the sync loop (a segment's
        useful/occupied steps and its retires land one tick later in
        ``TickReport``), but per-request completions, virtual-clock
        stamps, and end-of-run ledger totals are identical — pinned
        uid-for-uid in tests/test_scheduler.py."""
        done: List[InflightCompleted] = list(self._shed)
        shed = len(done)
        self._shed = []
        self._flow_tick = self._esc_tick = 0
        self._flow_cost_tick = 0.0
        useful = total = occupied = retired = 0
        quarantined = evicted = requeued = 0
        for pool in self._pools.values():
            if pool._pending is not None:
                st = pool.retire_pending()
                retired += st.retired
                useful += st.useful
                total += self.slots * self.seg
                occupied += st.occupied * self.seg
                quarantined += st.quarantined
                evicted += st.deadline_evicted
                requeued += st.requeued
        probe_cost, admitted, pool_probe, dropped, probe_nonfinite = \
            self._admit_tick()
        done.extend(dropped)
        cost = probe_cost + self._flow_cost_tick
        for key, pool in self._pools.items():
            if not pool.busy():
                continue
            seg_cost = self.oracle.segment_cost(pool.shape, self.seg,
                                                self.slots, self.stages)
            if self.fault_injector is not None:
                # keyed on the dispatch sequence (see _step_sync)
                seg_cost = self.fault_injector.inflate_segment_cost(
                    self.dispatches, seg_cost)
            self.dispatches += 1
            cost += seg_cost
            pool.launch_segment(self.now + pool_probe.get(key, 0.0)
                                + seg_cost)
        for pool in self._pools.values():
            done.extend(pool.finalize_retired())
            # staged-segment retire stats (st.retired above) never see
            # flow rows — they retire straight out of finalize
            retired += pool.flow_retired_last
        self._finish_tick(cost=cost, probe_cost=probe_cost,
                          admitted=admitted,
                          retired=retired + shed + len(dropped),
                          useful=useful, total=total, occupied=occupied,
                          quarantined=quarantined,
                          deadline_evicted=evicted + len(dropped),
                          requeued=requeued, shed=shed,
                          probe_nonfinite=probe_nonfinite,
                          flow_served=self._flow_tick,
                          escalated=self._esc_tick)
        return done

    # ----------------------------------------------------- convenience ----
    def run(self, xs) -> List[InflightCompleted]:
        """Submit a batch at the current instant and drive to completion,
        returning results ordered by submission (uid join)."""
        uids = [self.submit(x) for x in np.asarray(xs)]
        results: Dict[int, InflightCompleted] = {}
        while self.pending:
            for c in self.step():
                results[c.uid] = c
        return [results[u] for u in uids]
