"""The per-layer metrics that read the program's own spans
(``bench/program_spans.py``, ``metrics/fetch_*``, ``queue_wait_ms.lat``,
``tick_self_ms.lat``), on a trace of a tiny in-flight scheduler recorded
here on the CPU, each ``step()`` inside a ``bench.step`` span as the
harness wraps it."""
import importlib.util
import os
import types

import jax
import pytest

import counts
import program_spans
import xplane

METRICS = os.path.join(os.path.dirname(os.path.abspath(counts.__file__)),
                       "metrics")
PROBE_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "probe.xplane.pb")
READERS = ("fetch_ms", "fetch_ms.lat", "fetch_mb_per_req",
           "queue_wait_ms.lat", "tick_self_ms.lat")
PHASES = ("inflight.tick", "inflight.admit", "inflight.launch",
          "inflight.meta_wait", "inflight.readout", "inflight.fetch")
N, SLOTS, D = 11, 4, 24     # fixed K: groups of 4, 4 and 3 (padded to 4)


def _read(name, tr):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name.replace('.', '_')}", os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(types.SimpleNamespace(trace=tr))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from repro.launch.engine import EngineConfig
    from repro.launch.scheduler import InflightScheduler
    from repro.launch.workload import heterogeneous_requests, toy_classifier

    sched = InflightScheduler(
        toy_classifier(d=D), EngineConfig(buckets=(4,), controller="fixed",
                                          fixed_K=4, solver="euler"),
        slots=SLOTS, seg=2)
    for x in heterogeneous_requests(N, D, seed=5):
        sched.submit(x)
    trace_dir = str(tmp_path_factory.mktemp("spans"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    done, admitted = [], 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        while sched.pending:
            with jax.profiler.TraceAnnotation("bench.step"):
                done += sched.step()
            admitted += sched.last_report.admitted
    finally:
        jax.profiler.stop_trace()
    return xplane.load(trace_dir), done, admitted


def test_every_phase_span_is_in_the_trace(served):
    tr, done, _ = served
    for name in PHASES:
        assert program_spans.spans(tr, name), name
    ticks = program_spans.spans(tr, "inflight.tick")
    assert [st["step_num"] for _, _, st in ticks] == list(range(len(ticks)))


def test_span_counters_add_up(served):
    tr, done, admitted = served
    assert len(done) == admitted == N
    rows = lambda name: [st["rows"] for _, _, st in
                         program_spans.spans(tr, name)]
    assert sum(rows("inflight.admit")) == admitted
    assert sum(rows("inflight.readout")) == sum(rows("inflight.fetch")) \
        == len(done)
    row_bytes = done[0].outputs.nbytes
    fetches = program_spans.spans(tr, "inflight.fetch")
    assert [(st["rows"], st["width"]) for _, _, st in fetches] == \
        [(4, 4), (4, 4), (3, 4)]
    assert sum(st["bytes"] for _, _, st in fetches) == \
        sum(st["width"] for _, _, st in fetches) * row_bytes


def test_readers_on_the_program_trace(served):
    tr, done, _ = served
    row_bytes = done[0].outputs.nbytes
    got = {name: _read(name, tr) for name in READERS}
    assert got["fetch_mb_per_req"] == pytest.approx(
        12 * row_bytes / N * 1e-6)
    fetch = sum(e - s for s, e, _ in program_spans.spans(
        tr, "inflight.fetch")) * 1e-6 / N
    assert got["fetch_ms"] == got["fetch_ms.lat"] == pytest.approx(fetch)
    waits = program_spans.spans(tr, "inflight.admit")
    assert got["queue_wait_ms.lat"] == pytest.approx(
        sum(st["wait_ms"] for _, _, st in waits) / N)
    # later admissions waited longer: the whole run was queued at once
    assert 0 < waits[0][2]["wait_ms"] / 4 < waits[-1][2]["wait_ms"] / 3
    ticks = program_spans.spans(tr, "inflight.tick")
    mean_tick = sum(e - s for s, e, _ in ticks) / len(ticks) * 1e-6
    assert 0 < got["tick_self_ms.lat"] < mean_tick


def test_readers_find_nothing_without_program_spans():
    tr = xplane.Trace(PROBE_TRACE)
    for name in READERS:
        assert _read(name, tr) is None, name
