"""Spans of the in-flight scheduler's served path (launch/scheduler.py).

The phases of a tick are ``jax.profiler`` annotations in the profiler's
own trace, so the pins here are: a running profiler changes nothing
served (both ticks), the wall-clock queue stamp behind the
``inflight.admit`` span's ``wait_ms`` restarts when a request re-enters
the queue, and the pool's programs and the fused kernel keep the names a
profile shows them under (``bench/metric_kit.py`` keys on ``jit_run``
and ``jit_probe``).
"""
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.launch.scheduler as scheduler
from repro.distributed.fault import FaultInjector
from repro.launch.engine import EngineConfig
from repro.launch.scheduler import InflightScheduler
from repro.launch.workload import heterogeneous_requests, toy_classifier

ECFG = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=8,
                    solver="euler", fused=True)
# a width of its own: the fused kernel's trace cache is global, and
# other suites pin one trace per (shape, seg) cell at their widths
D = 12


def _sched(**kw):
    return InflightScheduler(toy_classifier(d=D), ECFG, slots=4, seg=2,
                             **kw)


def _serve(sched, xs):
    for x in xs:
        sched.submit(x)
    done = []
    while sched.pending:
        done += sched.step()
    return {c.uid: c for c in done}


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
def test_profiler_on_and_off_serve_the_same(overlap, tmp_path):
    xs = heterogeneous_requests(10, D, seed=1)
    off = _serve(_sched(overlap=overlap), xs)
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = _serve(_sched(overlap=overlap), xs)
    finally:
        jax.profiler.stop_trace()
    assert off.keys() == on.keys() and len(off) == 10
    for uid, a in off.items():
        b = on[uid]
        assert (a.K, a.nfe, a.status, a.t_submit, a.t_admit, a.t_done,
                a.segments) == (b.K, b.nfe, b.status, b.t_submit, b.t_admit,
                                b.t_done, b.segments)
        np.testing.assert_array_equal(a.outputs, b.outputs)


class _Recorder:
    """Stands in for ``TraceAnnotation``: records each span's name and
    arguments."""

    calls = []

    def __init__(self, name, **args):
        self.calls.append((name, args))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
def test_queue_wait_restarts_when_a_request_is_requeued(overlap,
                                                        monkeypatch):
    """Every request diverges once (transient poison) and goes back to
    the front of the queue; its second admission's ``wait_ms`` counts
    from the requeue, not from its submission."""
    now = [0.0]
    monkeypatch.setattr(scheduler, "time",
                        types.SimpleNamespace(perf_counter=lambda: now[0]))
    monkeypatch.setattr(_Recorder, "calls", [])
    monkeypatch.setattr(scheduler, "TraceAnnotation", _Recorder)
    inj = FaultInjector(seed=1, nan_uid_frac=1.0, nan_transient=True)
    sched = _sched(overlap=overlap, fault_injector=inj)
    for x in heterogeneous_requests(4, D, seed=2):
        sched.submit(x)                        # queued at t = 0
    admits = lambda: [a for n, a in _Recorder.calls if n == "inflight.admit"]
    requeued_at = None
    for t in (100.0, 107.0, 115.0, 124.0):
        now[0] = t
        sched.step()
        if sched.last_report.requeued and requeued_at is None:
            requeued_at = t
    assert requeued_at is not None and sched.total_requeued == 4
    first, second = admits()[:2]
    assert first == {"rows": 4, "wait_ms": pytest.approx(4 * 100e3)}
    # the sync tick requeues after its admission and re-admits next
    # tick; the overlap tick requeues in its retire phase and re-admits
    # in the same tick
    readmit_at = requeued_at + 7.0 if not overlap else requeued_at
    assert second["rows"] == 4
    assert second["wait_ms"] == pytest.approx(4 * 1e3
                                              * (readmit_at - requeued_at))
    assert not sched._queued_at


def test_pool_programs_keep_their_names():
    sched = _sched()
    assert len(sched.run(heterogeneous_requests(3, D, seed=3))) == 3
    (pool,) = sched._pools.values()
    probe, embed, segment, readout = pool._cells()
    p, xs = sched.params, pool._xs_dev
    lowered = [
        probe.lower(p, xs),
        embed.lower(p, xs),
        segment.lower(p, xs, pool.z, jnp.asarray(pool.k),
                      jnp.asarray(pool.Ks), jnp.asarray(pool.eps), pool.fs),
        readout.lower(p, xs, pool.z),
    ]
    names = [re.search(r"module @(\S+)", low.as_text()).group(1)
             for low in lowered]
    assert names == ["jit_probe", "jit_embed", "jit_run", "jit_readout"]


def test_fused_kernel_is_named_for_tpu():
    """Lowered for the TPU (no chip needed), the update kernel carries
    its own name whatever jitted program wraps it."""
    from repro.kernels.hyper_step.hyper_step import LANES, rk_update_batched

    plane = jax.ShapeDtypeStruct((2, 8, LANES), jnp.float32)
    row = lambda dt: jax.ShapeDtypeStruct((2,), dt)

    def wrapper(z, r, eps, epsp, act):
        return rk_update_batched(z, (r,), None, eps, epsp, act, (1.0,),
                                 interpret=False)

    text = jax.jit(wrapper).trace(
        plane, plane, row(jnp.float32), row(jnp.float32),
        row(jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
    assert 'kernel_name = "fused_rk_update"' in text
