"""In-flight depth-continuous batching: the resumable segment solve
(core/integrate.py::solve_segment), the slot-pool scheduler
(launch/scheduler.py), arrival-trace workloads + replay accounting
(launch/workload.py), and the BENCH schema gate (benchmarks/run.py
--check).

The acceptance pins:
  * segment-by-segment == one ``solve_multirate`` call (fp32 allclose),
    mixed-K, with and without a hypersolver correction;
  * ONE fused-kernel trace per (shape, seg) cell across every
    occupancy/refill pattern a streaming trace produces;
  * the slot-axis-sharded pool (``solve_segment(mesh=)`` /
    ``InflightScheduler(mesh=)``) reproduces the single-device results
    bit-for-bit on a forced 4-device CPU mesh, still one kernel trace
    per (shape, seg, mesh) cell (subprocess — the main test process
    keeps one device).
"""
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FixedGrid, Integrator, SegmentCarry, get_tableau, make_segment_carry,
)
from repro.kernels.hyper_step.ops import TRACE_COUNTS
from repro.launch.engine import DepthModel, EngineConfig, MultiRateEngine
from repro.launch.oracle import RooflineOracle, SequentialEvalOracle
from repro.launch.scheduler import InflightScheduler
from repro.launch.workload import (
    TraceReport, bursty_trace, heterogeneous_requests, latency_stats,
    poisson_trace, replay_engine, replay_scheduler,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _field(s, z):
    return -z * jax.nn.softplus(jnp.mean(z, axis=-1, keepdims=True))


G = lambda eps, s, z, dz: 0.25 * z + 0.1 * dz


def _toy_model(fused=False, g=None):
    def field_of(p, x):
        k = jax.nn.softplus(jnp.mean(x, axis=-1, keepdims=True))
        return lambda s, z: -z * k

    return DepthModel(
        embed=lambda p, x: x + 0.0,
        field_of=field_of,
        readout=lambda p, x, zT: zT,
        integ=Integrator(tableau=get_tableau("euler"), g=g, fused=fused),
    )


# ------------------------------------------------- solve_segment parity ----

@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("with_g", [False, True])
@pytest.mark.parametrize("seg", [1, 2, 3, 8])
def test_solve_segment_parity_with_solve_multirate(fused, with_g, seg):
    """ACCEPTANCE: driving a mixed-K batch to completion segment-by-
    segment is allclose (fp32) to ONE solve_multirate call — with and
    without a hypersolver correction, fused and unfused, for seg both
    dividing and not dividing the mesh lengths."""
    g = G if with_g else None
    integ = Integrator(get_tableau("heun"), g=g, fused=fused)
    z0 = jax.random.normal(jax.random.PRNGKey(0), (5, 17))
    Ks = jnp.asarray([1, 2, 5, 8, 3], jnp.int32)
    fs = _field(0.0, z0)
    ref = integ.solve_multirate(_field, z0, (0.0, 1.0), Ks, 8,
                                first_stage=fs)
    carry = make_segment_carry(z0, Ks, (0.0, 1.0), first_stage=fs)
    fin = None
    for _ in range(-(-8 // seg)):
        carry, fin = integ.solve_segment(_field, carry, seg)
    assert bool(jnp.all(fin))
    np.testing.assert_allclose(np.asarray(carry.z), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_solve_segment_refill_midflight_matches_fresh_solve():
    """A slot retired and refilled mid-flight (new z row, k=0, new K)
    integrates its own mesh exactly as a fresh solve would — the
    resumability the scheduler's admit-between-segments relies on."""
    integ = Integrator(get_tableau("euler"), fused=True)
    z0 = jax.random.normal(jax.random.PRNGKey(1), (3, 9))
    carry = make_segment_carry(z0, jnp.asarray([2, 6, 0]), (0.0, 1.0))
    carry, fin = integ.solve_segment(_field, carry, 2)
    assert np.asarray(fin).tolist() == [True, False, True]
    # refill slots 0 (finished) and 2 (was empty) with new requests
    z_new = jax.random.normal(jax.random.PRNGKey(2), (2, 9))
    idx = jnp.asarray([0, 2])
    carry = SegmentCarry(
        z=carry.z.at[idx].set(z_new),
        k=carry.k.at[idx].set(0),
        Ks=carry.Ks.at[idx].set(jnp.asarray([4, 3])),
        eps=carry.eps.at[idx].set(jnp.asarray([0.25, 1.0 / 3.0])),
        first_stage=None)
    for _ in range(3):
        carry, fin = integ.solve_segment(_field, carry, 2)
    assert bool(jnp.all(fin))
    for j, (i, K) in enumerate(((0, 4), (2, 3))):
        ref = integ.solve(_field, z_new[j][None],
                          FixedGrid.over(0.0, 1.0, K), return_traj=False)
        np.testing.assert_allclose(np.asarray(carry.z[i]),
                                   np.asarray(ref[0]), rtol=1e-6, atol=1e-6)


def test_make_segment_carry_empty_slots_stay_inert():
    """Ks == 0 marks an empty slot: frozen state, counter pinned at 0, no
    NaN/inf leaking from the padded eps."""
    integ = Integrator(get_tableau("euler"), fused=True)
    z0 = jnp.ones((3, 4))
    carry = make_segment_carry(z0, jnp.asarray([2, 0, 3]), (0.0, 1.0))
    assert np.all(np.isfinite(np.asarray(carry.eps)))
    carry, fin = integ.solve_segment(_field, carry, 4)
    assert np.asarray(fin).tolist() == [True, True, True]
    np.testing.assert_array_equal(np.asarray(carry.z[1]), np.ones(4))
    assert np.asarray(carry.k).tolist() == [2, 0, 3]


# ---------------------------------------------------- compile accounting ----

def test_one_kernel_trace_per_shape_seg_cell_across_refills():
    """ACCEPTANCE: a full streaming replay — admissions, retirements,
    partial occupancy, every refill pattern the trace produces — traces
    the fused kernel exactly ONCE per (shape, seg) cell."""
    xs = heterogeneous_requests(24, 8, seed=2)
    trace = poisson_trace(xs, rate=0.3, seed=4)
    ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, fused=True)
    sched = InflightScheduler(_toy_model(fused=True), ecfg, slots=4, seg=2)
    before = TRACE_COUNTS["fused_rk_update"]
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        rep = replay_scheduler(sched, trace)
    assert len(rep.records) == 24
    assert TRACE_COUNTS["fused_rk_update"] == before + 1, (
        "occupancy/refill pattern leaked into the segment jit cell")
    # a second shape opens a second (shape, seg) cell — exactly one more
    sched.run(np.zeros((3, 5), np.float32) - 2.0)
    assert TRACE_COUNTS["fused_rk_update"] == before + 2


# -------------------------------------------------- scheduler vs engine ----

def test_scheduler_outputs_and_nfe_match_engine():
    """Same controller + buckets through both loops: request-for-request
    equal K, equal NFE accounting, numerically matching outputs."""
    xs = heterogeneous_requests(18, 8, seed=1)
    ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=6)
    res_e = MultiRateEngine(_toy_model(), ecfg).run(xs)
    res_s = InflightScheduler(_toy_model(), ecfg, slots=6, seg=2).run(xs)
    assert [r.uid for r in res_s] == [r.uid for r in res_e]
    for a, b in zip(res_e, res_s):
        assert (a.K, a.nfe) == (b.K, b.nfe)
        np.testing.assert_allclose(a.outputs, b.outputs, rtol=1e-6,
                                   atol=1e-6)


def test_scheduler_fixed_controller_and_hyper_solver_paths():
    ecfg = EngineConfig(buckets=(4,), controller="fixed", fixed_K=4)
    res = InflightScheduler(_toy_model(), ecfg, slots=3, seg=2).run(
        heterogeneous_requests(5, 6, seed=3))
    assert all(r.K == 4 and r.nfe == 4 for r in res)

    hyper = EngineConfig(buckets=(2, 4, 8), tol=1e-1, solver="hyper_euler")
    sched = InflightScheduler(_toy_model(g=lambda e, s, z, dz: 0.3 * z),
                              hyper, slots=4, seg=2)
    res = sched.run(heterogeneous_requests(6, 6, seed=4))
    assert type(sched.controller).__name__ == "HypersolverResidualController"
    assert all(r.nfe == r.K for r in res)  # probe fully reused

    with pytest.raises(ValueError):
        InflightScheduler(_toy_model(), hyper)  # hyper solver needs g


def test_easy_request_escapes_a_busy_pool_early():
    """THE motivating property: a K=2 request admitted while a K=16
    request is mid-flight exits after its own segments instead of
    waiting out the long request (the drain engine cannot do this when
    both land in one batch)."""
    ecfg = EngineConfig(buckets=(2, 16), tol=1e-2, max_batch=2)
    sched = InflightScheduler(_toy_model(), ecfg, slots=2, seg=2)
    hard = np.full((6,), 3.0, np.float32)
    easy = np.full((6,), -2.0, np.float32)
    uid_hard = sched.submit(hard)
    done = sched.step()           # hard admitted, in flight
    assert not done
    uid_easy = sched.submit(easy)
    finished = {}
    while sched.pending:
        for c in sched.step():
            finished[c.uid] = c
    assert finished[uid_hard].K == 16 and finished[uid_easy].K == 2
    assert finished[uid_easy].t_done < finished[uid_hard].t_done
    # and the drain engine, forced to pack them together, cannot:
    eng = MultiRateEngine(_toy_model(), ecfg)
    eng.submit(hard), eng.submit(easy)
    eng.step()
    assert eng.last_report.batches == 1
    assert eng.last_report.finish_offset[1] == eng.last_report.finish_offset[2]


def test_submit_future_t_refused_while_busy_allowed_when_idle():
    """A future-t submit idle-jumps the clock only when nothing is in
    flight; with work pending it is refused — jumping mid-flight would
    bill in-flight requests for time no segment ran."""
    ecfg = EngineConfig(buckets=(2, 4), tol=1e-2)
    sched = InflightScheduler(_toy_model(), ecfg, slots=2, seg=1)
    sched.submit(np.full((4,), 3.0, np.float32), t=5.0)  # idle: jump
    assert sched.now == 5.0
    sched.step()
    assert sched.pending  # K=4 hard request still mid-flight at seg=1
    with pytest.raises(ValueError, match="misattribute"):
        sched.submit(np.full((4,), -2.0, np.float32), t=sched.now + 100.0)
    while sched.pending:
        sched.step()
    assert sched.now < 100.0


def test_scheduler_handles_mixed_shapes_and_queue_overflow():
    """More requests than slots queue up and drain FIFO per shape; a
    second shape gets its own pool without blocking the first."""
    ecfg = EngineConfig(buckets=(2, 4), tol=1e-2)
    sched = InflightScheduler(_toy_model(), ecfg, slots=2, seg=2)
    uids_a = [sched.submit(np.full((3,), -2.0, np.float32))
              for _ in range(5)]
    uid_b = sched.submit(np.full((7,), -2.0, np.float32))
    results = {}
    while sched.pending:
        for c in sched.step():
            results[c.uid] = c
    assert sorted(results) == sorted(uids_a + [uid_b])
    assert results[uid_b].outputs.shape == (7,)
    # FIFO within a shape: earlier submissions never finish after later ones
    admits = [results[u].t_admit for u in uids_a]
    assert admits == sorted(admits)


def test_scheduler_same_shape_mixed_dtypes_get_separate_pools():
    """Same-shape requests of a different dtype open their own pool
    instead of silently casting into the first admission's storage —
    the scheduler's explicit version of jit's dtype retrace boundary."""
    ecfg = EngineConfig(buckets=(2, 4), tol=1e-2)
    sched = InflightScheduler(_toy_model(), ecfg, slots=2, seg=2)
    u32 = sched.submit(np.full((4,), -2.0, np.float32))
    u64 = sched.submit(np.full((4,), -2.25, np.float64))
    results = {}
    while sched.pending:
        for c in sched.step():
            results[c.uid] = c
    assert len(sched._pools) == 2
    # fractional float64 value survived (no truncation through a latched
    # pool dtype); outputs match the engine's on the same lone request
    res_e = MultiRateEngine(_toy_model(), ecfg).run(
        np.full((1, 4), -2.25, np.float64))
    np.testing.assert_allclose(np.asarray(results[u64].outputs, np.float64),
                               np.asarray(res_e[0].outputs, np.float64),
                               rtol=1e-6, atol=1e-6)


# ------------------------------------------------------- hot-swapped g ----

@pytest.mark.parametrize("overlap", [False, True],
                         ids=["sync", "overlap"])
def test_hot_swap_g_mid_flight_no_retrace(overlap):
    """ACCEPTANCE (PR 9): swapping correction params into a BUSY pool —
    sync and overlap loops — compiles nothing (TRACE_COUNTS frozen: the
    params are traced cell inputs), drains nothing, and is LIVE
    (post-swap completions differ from a never-swapped replay)."""
    from repro.launch.workload import toy_refinable_classifier

    ecfg = EngineConfig(controller="fixed", fixed_K=4, buckets=(4,))
    xs = heterogeneous_requests(16, 8, seed=5)
    trace = poisson_trace(xs, rate=0.25, seed=6)
    new_gp = jax.tree_util.tree_map(
        lambda l: l + 0.5, toy_refinable_classifier(d=8).g_params)

    def run(swap):
        sched = InflightScheduler(toy_refinable_classifier(d=8), ecfg,
                                  slots=4, seg=1, overlap=overlap)
        state = {"tick": 0, "before": None}

        def on_tick(s):
            state["tick"] += 1
            if swap and state["tick"] == 3:
                assert s.pending, "swap must land on a busy pool"
                state["before"] = TRACE_COUNTS["fused_rk_update"]
                s.hot_swap_g(new_gp)

        rep = replay_scheduler(sched, trace, on_tick=on_tick)
        if swap:
            assert state["before"] is not None
            assert TRACE_COUNTS["fused_rk_update"] == state["before"], (
                "hot_swap_g retraced a pool cell — params stopped being "
                "traced inputs")
        assert len(rep.records) == 16
        return {r.uid: r.outputs for r in rep.records}

    plain, swapped = run(False), run(True)
    assert set(plain) == set(swapped)
    assert any(not np.array_equal(plain[u], swapped[u]) for u in plain), (
        "swapped params never reached the pool cells")


# ------------------------------------------------- sharded slot pools ----

_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import warnings
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Integrator, get_tableau, make_segment_carry
    from repro.kernels.hyper_step.ops import TRACE_COUNTS
    from repro.launch.engine import DepthModel, EngineConfig
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.scheduler import InflightScheduler
    from repro.launch.workload import (
        heterogeneous_requests, latency_stats, poisson_trace,
        replay_scheduler,
    )

    assert jax.device_count() == 4, jax.device_count()
    mesh = make_serving_mesh(4)
    f = lambda s, z: -z * jax.nn.softplus(jnp.mean(z, -1, keepdims=True))
    G = lambda eps, s, z, dz: 0.25 * z + 0.1 * dz

    # ACCEPTANCE: solve_segment(mesh=) segment-by-segment == one
    # solve_multirate call, fp32, with and without a correction
    for g in (None, G):
        integ = Integrator(get_tableau("heun"), g=g, fused=True)
        z0 = jax.random.normal(jax.random.PRNGKey(0), (8, 17))
        Ks = jnp.asarray([1, 2, 5, 8, 3, 4, 8, 2], jnp.int32)
        fs = f(0.0, z0)
        ref = integ.solve_multirate(f, z0, (0.0, 1.0), Ks, 8,
                                    first_stage=fs)
        carry = make_segment_carry(z0, Ks, (0.0, 1.0), first_stage=fs)
        fin = None
        for _ in range(4):
            carry, fin = integ.solve_segment(f, carry, 2, mesh=mesh)
        assert bool(jnp.all(fin))
        np.testing.assert_allclose(np.asarray(carry.z), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)
    print("SHARDED_SEGMENT_PARITY_OK")

    # a slot count the mesh axis cannot split raises the clear error
    bad = make_segment_carry(jnp.ones((6, 4)), jnp.asarray([2] * 6),
                             (0.0, 1.0))
    try:
        Integrator(get_tableau("euler")).solve_segment(f, bad, 2,
                                                       mesh=mesh)
    except ValueError as e:
        assert "does not divide" in str(e), e
        print("SHARDED_SEGMENT_DIVISIBILITY_OK")

    # sharded pool replay == single-device pool replay, request for
    # request, and ONE fused-kernel trace for the (shape, seg, mesh)
    # cell across every refill pattern the trace produces
    def field_of(p, x):
        k = jax.nn.softplus(jnp.mean(x, axis=-1, keepdims=True))
        return lambda s, z: -z * k

    def model():
        return DepthModel(
            embed=lambda p, x: x + 0.0, field_of=field_of,
            readout=lambda p, x, zT: zT,
            integ=Integrator(get_tableau("euler"), fused=True))

    ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, fused=True)
    xs = heterogeneous_requests(24, 8, seed=2)
    trace = poisson_trace(xs, rate=0.5, seed=4)
    rep_1 = replay_scheduler(
        InflightScheduler(model(), ecfg, slots=8, seg=2), trace)
    before = TRACE_COUNTS["fused_rk_update"]
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        rep_4 = replay_scheduler(
            InflightScheduler(model(), ecfg, slots=8, seg=2, mesh=mesh),
            trace)
    assert TRACE_COUNTS["fused_rk_update"] == before + 1, (
        "refill pattern leaked into the sharded (shape, seg, mesh) cell")
    assert len(rep_4.records) == 24
    out_1 = {r.uid: r for r in rep_1.records}
    for r in rep_4.records:
        assert r.K == out_1[r.uid].K
        assert r.nfe == out_1[r.uid].nfe
        np.testing.assert_allclose(r.outputs, out_1[r.uid].outputs,
                                   rtol=1e-6, atol=1e-6)
    # equal global slots -> the virtual clock ticks identically
    s1, s4 = latency_stats(rep_1), latency_stats(rep_4)
    assert s1 == s4, (s1, s4)
    print("SHARDED_POOL_REPLAY_OK")

    # the pipelined overlap loop on the SAME forced mesh: completions
    # uid-for-uid bitwise identical to the sync sharded replay
    rep_o = replay_scheduler(
        InflightScheduler(model(), ecfg, slots=8, seg=2, mesh=mesh,
                          overlap=True), trace)
    assert len(rep_o.records) == 24
    out_4 = {r.uid: r for r in rep_4.records}
    for r in rep_o.records:
        ref = out_4[r.uid]
        assert r.K == ref.K and r.nfe == ref.nfe
        assert (r.t_submit, r.t_admit, r.t_done) == (
            ref.t_submit, ref.t_admit, ref.t_done)
        assert np.array_equal(np.asarray(r.outputs),
                              np.asarray(ref.outputs))
    assert latency_stats(rep_o) == s4
    print("SHARDED_OVERLAP_PARITY_OK")

    # hot-swapping correction params into a BUSY sharded pool (sync and
    # overlap) compiles nothing and is live — the params-are-inputs
    # invariant holds per (shape, seg, mesh) cell too
    from repro.launch.workload import toy_refinable_classifier

    pecfg = EngineConfig(controller="fixed", fixed_K=4, buckets=(4,))
    pxs = heterogeneous_requests(16, 8, seed=5)
    ptrace = poisson_trace(pxs, rate=0.25, seed=6)
    new_gp = jax.tree_util.tree_map(
        lambda l: l + 0.5, toy_refinable_classifier(d=8).g_params)

    def hot_run(swap, overlap):
        sched = InflightScheduler(toy_refinable_classifier(d=8), pecfg,
                                  slots=8, seg=1, mesh=mesh,
                                  overlap=overlap)
        state = {"tick": 0, "before": None}

        def on_tick(s):
            state["tick"] += 1
            if swap and state["tick"] == 3:
                assert s.pending
                state["before"] = TRACE_COUNTS["fused_rk_update"]
                s.hot_swap_g(new_gp)

        rep = replay_scheduler(sched, ptrace, on_tick=on_tick)
        if swap:
            assert state["before"] is not None
            assert TRACE_COUNTS["fused_rk_update"] == state["before"], (
                "hot_swap_g retraced a sharded pool cell")
        assert len(rep.records) == 16
        return {r.uid: r.outputs for r in rep.records}

    for overlap in (False, True):
        plain = hot_run(False, overlap)
        swapped = hot_run(True, overlap)
        assert set(plain) == set(swapped)
        assert any(not np.array_equal(plain[u], swapped[u])
                   for u in plain)
    print("SHARDED_HOTSWAP_NO_RETRACE_OK")
""")


def test_sharded_slot_pool_debug_mesh_subprocess():
    """ACCEPTANCE: on a forced 4-device CPU mesh, ``solve_segment(mesh=)``
    keeps exact parity with ``solve_multirate``, the sharded scheduler
    reproduces the single-device replay request-for-request, one kernel
    trace serves the (shape, seg, mesh) cell across refills, and the
    indivisible slot count raises the clear error (subprocess — the main
    test process keeps one device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=REPO_ROOT)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    for marker in ("SHARDED_SEGMENT_PARITY_OK",
                   "SHARDED_SEGMENT_DIVISIBILITY_OK",
                   "SHARDED_POOL_REPLAY_OK",
                   "SHARDED_OVERLAP_PARITY_OK",
                   "SHARDED_HOTSWAP_NO_RETRACE_OK"):
        assert marker in out, (marker, out[-4000:])


# ---------------------------------------------------------- workloads ----

def test_trace_generators_are_seeded_and_ordered():
    xs = heterogeneous_requests(12, 4, seed=0)
    t1 = poisson_trace(xs, rate=0.5, seed=7)
    t2 = poisson_trace(xs, rate=0.5, seed=7)
    assert [a.t for a in t1] == [a.t for a in t2]
    assert all(a.t <= b.t for a, b in zip(t1, t1[1:]))
    tb = bursty_trace(xs, burst=4, gap=10.0, seed=7)
    assert len(tb) == 12
    assert len({round(a.t, 6) for a in tb}) == 3  # 3 bursts, zero `within`
    with pytest.raises(ValueError):
        poisson_trace(xs, rate=0.0)


def test_heterogeneous_requests_difficulty_split():
    xs = heterogeneous_requests(10, 4, seed=0, interleave=False)
    assert xs.shape == (10, 4) and xs.dtype == np.float32
    assert xs[:5].mean() < -1.5 < 1.5 < xs[5:].mean()


def test_replay_accounting_invariants():
    """Both replays conserve requests and keep a sane time ordering:
    submit <= admit <= done per record; waste = total - useful >= 0."""
    xs = heterogeneous_requests(16, 6, seed=5)
    trace = poisson_trace(xs, rate=0.3, seed=6)
    ecfg = EngineConfig(buckets=(2, 4, 8), tol=5e-3, max_batch=4)
    rep_e = replay_engine(MultiRateEngine(_toy_model(), ecfg), trace)
    rep_s = replay_scheduler(
        InflightScheduler(_toy_model(), ecfg, slots=4, seg=2), trace)
    for rep in (rep_e, rep_s):
        assert len(rep.records) == 16
        for r in rep.records:
            assert r.t_submit <= r.t_admit <= r.t_done
        assert rep.waste_steps >= 0
        assert rep.useful_steps == sum(r.K for r in rep.records)
        stats = latency_stats(rep)
        for key in ("p50_latency", "p99_latency", "p99_queue_wait",
                    "throughput", "waste_steps", "waste_frac"):
            assert key in stats
        assert stats["p50_latency"] <= stats["p99_latency"]
    # identical traffic + policy -> identical outputs across the loops
    out_e = {r.uid: r.outputs for r in rep_e.records}
    for r in rep_s.records:
        np.testing.assert_allclose(r.outputs, out_e[r.uid], rtol=1e-6,
                                   atol=1e-6)


# --------------------------------------------------------- cost oracle ----

def test_pool_completions_stamped_with_own_cost_only():
    """BUGFIX pin: pools are concurrent cells, so a completion is stamped
    with only ITS pool's probe + segment cost — per-request latency must
    not depend on (shape, dtype) key insertion order. The pre-fix clock
    accumulated segment cost across pools in dict-iteration order,
    billing the second-iterated pool's completion 2x the first's."""
    ecfg = EngineConfig(buckets=(2,), controller="fixed", fixed_K=2)
    for order in ((3, 5), (5, 3)):
        sched = InflightScheduler(_toy_model(), ecfg, slots=2, seg=2)
        for d in order:
            sched.submit(np.full((d,), -2.0, np.float32))
        done = sched.step()        # K=2 completes within one seg=2 tick
        assert len(done) == 2
        # euler stages=1, seg=2, no probe under the fixed controller:
        # each pool's own cumulative cost this tick is exactly 2.0
        assert [c.t_done for c in done] == [2.0, 2.0], (order, done)
        # the tick's resource ledger still sums BOTH pools' segments
        assert sched.total_cost == 4.0


def test_drain_occupancy_invariant():
    """BUGFIX pin: drain-engine occupancy is 1.0 by construction — both
    for replay_engine's reports and for a TraceReport built WITHOUT
    occupied_steps (the old default of 0 reported 0.0)."""
    xs = heterogeneous_requests(8, 6, seed=5)
    rep = replay_engine(
        MultiRateEngine(_toy_model(), EngineConfig(buckets=(2, 4, 8),
                                                   tol=5e-3)),
        poisson_trace(xs, rate=0.3, seed=6))
    assert rep.total_steps > 0 and rep.occupancy == 1.0
    bare = TraceReport(records=rep.records, total_cost=1.0, probe_cost=0.0,
                       useful_steps=3, total_steps=4, makespan=1.0)
    assert bare.occupancy == 1.0
    assert latency_stats(bare)["occupancy"] == 1.0
    # an explicitly-counted pool report still reports its true fraction
    part = TraceReport(records=rep.records, total_cost=1.0, probe_cost=0.0,
                       useful_steps=3, total_steps=4, makespan=1.0,
                       occupied_steps=2)
    assert part.occupancy == 0.5


def test_sequential_oracle_is_a_pure_relabel():
    """Explicitly passing SequentialEvalOracle reproduces the default
    clock bit-for-bit through BOTH replay drivers (the oracle refactor
    did not change the default path's numbers)."""
    xs = heterogeneous_requests(20, 6, seed=5)
    trace = poisson_trace(xs, rate=0.3, seed=6)
    ecfg = EngineConfig(buckets=(2, 4, 8), tol=5e-3, max_batch=4)
    base = latency_stats(replay_scheduler(
        InflightScheduler(_toy_model(), ecfg, slots=4, seg=2), trace))
    expl = latency_stats(replay_scheduler(
        InflightScheduler(_toy_model(), ecfg, slots=4, seg=2,
                          oracle=SequentialEvalOracle()), trace))
    assert base == expl
    base_e = latency_stats(replay_engine(
        MultiRateEngine(_toy_model(), ecfg), trace))
    expl_e = latency_stats(replay_engine(
        MultiRateEngine(_toy_model(), ecfg,
                        oracle=SequentialEvalOracle()), trace))
    assert base_e == expl_e
    assert base["cost_unit"] == base_e["cost_unit"] == "sequential_evals"


def test_sequential_oracle_reproduces_bench_scheduler_numbers():
    """ACCEPTANCE: replaying bench_scheduler's seeded poisson_seed3 trace
    under an explicit SequentialEvalOracle reproduces the committed
    BENCH_scheduler.json inflight row bit-for-bit — the committed
    sequential section is exactly what the default clock produces."""
    import json
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from benchmarks.bench_scheduler import D_FEAT, toy_classifier
    with open(os.path.join(REPO_ROOT, "BENCH_scheduler.json")) as fh:
        rows = json.load(fh)
    row = next(r for r in rows if r.get("mode") == "inflight"
               and r.get("trace") == "poisson_seed3")
    xs = heterogeneous_requests(int(row["requests"]), D_FEAT, seed=3)
    trace = poisson_trace(xs, rate=0.25, seed=103)
    ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=8,
                        solver="euler", fused=True)
    sched = InflightScheduler(toy_classifier("euler"), ecfg,
                              slots=int(row["slots"]), seg=int(row["seg"]),
                              oracle=SequentialEvalOracle())
    stats = latency_stats(replay_scheduler(sched, trace))
    for key, val in stats.items():
        assert row[key] == val, (key, row[key], val)


def test_roofline_oracle_prices_seg_and_width():
    """The roofline oracle prices seg=2*s strictly above seg=s for a busy
    pool, and pool width is PRICED (sublinearly — weight reads amortize
    across rows) where the sequential clock gives it away for free."""
    from repro.configs import get
    o = RooflineOracle(get("qwen3_8b"), ctx=4096)
    shape = (32,)
    for s in (1, 2, 4):
        assert o.segment_cost(shape, 2 * s, 8, 1) \
            > o.segment_cost(shape, s, 8, 1)
    t8, t16 = o.step_time(8), o.step_time(16)
    assert t8 < t16 < 2 * t8        # priced, but sublinear
    assert o.probe_cost(shape, 8, 2) == 2 * t8
    seq = SequentialEvalOracle()
    assert seq.segment_cost(shape, 2, 8, 1) \
        == seq.segment_cost(shape, 2, 9999, 1)   # width-free by design


def test_roofline_oracle_replay_stamps_device_us():
    """An end-to-end replay on the roofline clock: same policy decisions
    as the sequential clock (K/NFE are clock-independent), ledgers and
    stats tagged device_us."""
    from repro.configs import get
    o = RooflineOracle(get("qwen3_8b"), ctx=4096)
    ecfg = EngineConfig(buckets=(2, 4, 8), tol=5e-3, max_batch=4)
    xs = heterogeneous_requests(12, 6, seed=5)
    # same relative load as the sequential replay: rate converts by the
    # pool's per-step price, so admission dynamics are congruent
    t_seq = poisson_trace(xs, rate=0.3, seed=6)
    t_us = poisson_trace(xs, rate=0.3 / o.step_time(4), seed=6)
    rep_seq = replay_scheduler(
        InflightScheduler(_toy_model(), ecfg, slots=4, seg=2), t_seq)
    rep_us = replay_scheduler(
        InflightScheduler(_toy_model(), ecfg, slots=4, seg=2, oracle=o),
        t_us)
    assert rep_us.cost_unit == "device_us"
    assert latency_stats(rep_us)["cost_unit"] == "device_us"
    # policy (which K each request gets) does not depend on the clock
    k_seq = {r.uid: r.K for r in rep_seq.records}
    assert {r.uid: r.K for r in rep_us.records} == k_seq
    # step COUNTS are clock-independent; COSTS scale with the step price
    assert rep_us.useful_steps == rep_seq.useful_steps
    assert rep_us.total_cost > rep_seq.total_cost


def test_autotune_cell_structure_and_hillclimb_keeps_best():
    """The knob autotuner returns a persisted-shape verdict: chosen knobs
    are JSON-clean, the hillclimb log carries verdicts, and the tuned
    score never regresses the baseline (hypothesis_loop keeps only
    CONFIRMED changes)."""
    from repro.launch.autotune import autotune_cell
    res = autotune_cell({"cell": "t4k", "arch": "qwen3_8b", "ctx": 4096},
                        budget="tiny",
                        steps=[("slots 8->16", "wider pool under load",
                                {"slots": 16})])
    assert res["mode"] == "tuner" and res["cost_unit"] == "device_us"
    assert set(res["chosen"]) == {"seg", "slots", "buckets"}
    assert res["p99_tuned"] <= res["p99_base"]
    verdicts = [r["verdict"] for r in res["log"][1:]]
    assert all(v == "CONFIRMED" or v.startswith("REFUTED")
               for v in verdicts)
    confirmed = [r["change"] for r in res["log"][1:]
                 if r["verdict"] == "CONFIRMED"]
    assert res["confirmed"] == confirmed
    assert (res["chosen"]["slots"] == 16) == ("slots 8->16" in confirmed)


# --------------------------------------------------------- BENCH schema ----

def _load_bench_run():
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    import benchmarks.run as bench_run
    return bench_run


def test_bench_schema_check_passes_on_committed_files():
    """benchmarks/run.py --check (the tier-1 CI gate) passes on the
    committed BENCH_*.json trajectory files."""
    bench_run = _load_bench_run()
    assert bench_run.check_bench_files(REPO_ROOT) == []


def test_bench_schema_check_catches_malformed_files(tmp_path):
    bench_run = _load_bench_run()
    errs = bench_run.check_bench_files(str(tmp_path))
    assert len(errs) == len(bench_run.BENCH_REQUIRED)  # all missing
    (tmp_path / "BENCH_kernels.json").write_text("{not json")
    (tmp_path / "BENCH_serve.json").write_text("[]")
    (tmp_path / "BENCH_scheduler.json").write_text(
        '[{"bench": "scheduler", "p99_latency": 1, "waste_steps": 0}]')
    errs = bench_run.check_bench_files(str(tmp_path))
    assert any("malformed" in e for e in errs)
    assert any("non-empty" in e for e in errs)
    assert any("verdict" in e for e in errs)
    # a corrupted scheduler file is an error STRING, not a crash
    (tmp_path / "BENCH_scheduler.json").write_text("{not json")
    errs = bench_run.check_bench_files(str(tmp_path))
    assert any("BENCH_scheduler.json" in e and "malformed" in e
               for e in errs)


# ------------------------------------------- overlap + donated carries ----

def test_overlap_replay_uid_for_uid_identical_to_sync():
    """ACCEPTANCE: the pipelined ``overlap=True`` loop replays a seeded
    Poisson trace uid-for-uid identical to the synchronous loop —
    bitwise-equal outputs, same K/nfe/segments, same virtual-clock
    stamps, same latency summary. The sync path is the oracle the
    pipeline is pinned against."""
    ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=8,
                        fused=True)
    xs = heterogeneous_requests(24, 8, seed=2)
    trace = poisson_trace(xs, rate=0.3, seed=4)
    rep_s = replay_scheduler(
        InflightScheduler(_toy_model(fused=True), ecfg, slots=4, seg=2),
        trace)
    rep_o = replay_scheduler(
        InflightScheduler(_toy_model(fused=True), ecfg, slots=4, seg=2,
                          overlap=True), trace)
    assert len(rep_o.records) == len(rep_s.records) == 24
    sync = {r.uid: r for r in rep_s.records}
    for r in rep_o.records:
        ref = sync[r.uid]
        assert (r.K, r.nfe) == (ref.K, ref.nfe)
        assert (r.t_submit, r.t_admit, r.t_done) == (
            ref.t_submit, ref.t_admit, ref.t_done)
        assert np.array_equal(np.asarray(r.outputs),
                              np.asarray(ref.outputs))
    assert latency_stats(rep_o) == latency_stats(rep_s)


def test_overlap_one_segment_retire_lag_and_cost_parity():
    """The overlap tick retires one segment LATE: completions finishing
    in segment N surface from step N+1 (launch first, read flags next
    tick), with the same per-pool cost stamps and ledger totals as the
    sync multi-pool pin above."""
    ecfg = EngineConfig(buckets=(2,), controller="fixed", fixed_K=2)
    sched = InflightScheduler(_toy_model(), ecfg, slots=2, seg=2,
                              overlap=True)
    for d in (3, 5):
        sched.submit(np.full((d,), -2.0, np.float32))
    assert sched.step() == []         # segments in flight, flags unread
    done = sched.step()               # lagged retire surfaces both
    assert len(done) == 2
    assert [c.t_done for c in done] == [2.0, 2.0]
    assert sched.total_cost == 4.0
    assert not sched.pending


def test_overlap_requires_multicore_host_is_documented():
    """The donate auto-default is platform-aware: off on the CPU client
    (where a donating call dispatches synchronously and would serialize
    the pipeline at launch), forced values win either way."""
    ecfg = EngineConfig(buckets=(2,), controller="fixed", fixed_K=2)
    assert InflightScheduler(_toy_model(), ecfg).donate is (
        jax.default_backend() != "cpu")
    assert InflightScheduler(_toy_model(), ecfg, donate=True).donate
    assert not InflightScheduler(_toy_model(), ecfg, donate=False).donate


def test_segment_cell_donates_carry_buffers():
    """ACCEPTANCE: the compiled segment cell reports the pool-sized
    carry buffers (z, first_stage) as donated — input/output aliasing
    in the compiled memory analysis, donated inputs deleted after the
    call, conditioning rows untouched."""
    m = _toy_model(fused=True)
    cell = m.integ.segment_cell(m.field_of, seg=2, donate=True)
    B, d = 4, 16
    xs = jnp.zeros((B, d), jnp.float32)
    z = jnp.ones((B, d), jnp.float32)
    fs = jnp.zeros((B, d), jnp.float32)
    k = jnp.zeros((B,), jnp.int32)
    Ks = jnp.full((B,), 4, jnp.int32)
    eps = jnp.full((B,), 0.25, jnp.float32)
    compiled = cell.lower(m.params, xs, z, k, Ks, eps, fs).compile()
    assert "input_output_alias" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= z.nbytes + fs.nbytes, (
        mem.alias_size_in_bytes)
    z2, fs2, meta = cell(m.params, xs, z, k, Ks, eps, fs)
    assert z.is_deleted() and fs.is_deleted()
    assert not xs.is_deleted()
    meta = np.array(meta)
    assert meta.shape == (3, B) and meta.dtype == np.int32
    np.testing.assert_array_equal(meta[0], [2, 2, 2, 2])   # k' after seg=2
    np.testing.assert_array_equal(meta[1], [0, 0, 0, 0])   # K=4 unfinished
    np.testing.assert_array_equal(meta[2], [0, 0, 0, 0])   # all finite


def test_retire_readout_gated_to_finished_rows():
    """BUGFIX pin: retirement reads out only the FINISHED rows (padded
    to a pow2 cell), never the full pool — the readout jit is traced at
    sub-pool widths and the pool records exactly those widths."""
    traced = []

    def readout(p, x, zT):
        traced.append(zT.shape[0])    # runs at TRACE time only
        return zT

    base = _toy_model(fused=True)
    model = DepthModel(embed=base.embed, field_of=base.field_of,
                       readout=readout, integ=base.integ)
    ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=8,
                        fused=True)
    xs = heterogeneous_requests(24, 8, seed=2)
    sched = InflightScheduler(model, ecfg, slots=8, seg=2)
    rep = replay_scheduler(sched, poisson_trace(xs, rate=0.5, seed=4))
    assert len(rep.records) == 24
    pool = next(iter(sched._pools.values()))
    assert traced, "readout never traced"
    assert set(traced) == pool._readout_widths
    # a streaming trace retires stragglers in sub-pool batches: the
    # pre-fix full-pool readout would have traced ONLY width 8
    assert min(traced) < sched.slots, traced
    assert all(w <= sched.slots and w & (w - 1) == 0 for w in traced)


# ------------------------------------------------------- tier-2 sweep ----

@pytest.mark.slow
def test_scheduler_seg_slots_sweep_parity_and_latency():
    """Tier-2: across (seg, slots) configs on a longer Poisson trace, the
    scheduler keeps exact policy parity with the engine and its mean
    queue wait stays at-or-below the drain loop's."""
    xs = heterogeneous_requests(64, 8, seed=8)
    trace = poisson_trace(xs, rate=0.25, seed=9)
    ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=8,
                        fused=True)
    rep_e = replay_engine(MultiRateEngine(_toy_model(fused=True), ecfg),
                          trace)
    out_e = {r.uid: r for r in rep_e.records}
    wait_e = np.mean([r.queue_wait for r in rep_e.records])
    for seg in (1, 2, 4):
        for slots in (4, 8):
            sched = InflightScheduler(_toy_model(fused=True), ecfg,
                                      slots=slots, seg=seg)
            rep_s = replay_scheduler(sched, trace)
            assert len(rep_s.records) == 64
            for r in rep_s.records:
                assert r.K == out_e[r.uid].K
                np.testing.assert_allclose(r.outputs, out_e[r.uid].outputs,
                                           rtol=1e-6, atol=1e-6)
            if slots == 8:
                wait_s = np.mean([r.queue_wait for r in rep_s.records])
                assert wait_s <= wait_e, (seg, slots, wait_s, wait_e)
