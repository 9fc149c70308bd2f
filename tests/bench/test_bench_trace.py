"""The trace reduction on a small recorded trace.

``data/probe.xplane.pb`` was recorded on one TPU v5 lite chip: five
rounds, each a ``bench.step`` span around a matmul program (``probe``), a
reduction (a lambda) and the fused update kernel, then a
``bench.collect`` span around a 268 MB float32 device-to-host copy."""
import os
import types

import pytest

import counts
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "probe.xplane.pb")
METRICS = os.path.join(os.path.dirname(os.path.abspath(counts.__file__)),
                       "metrics")


@pytest.fixture(scope="module")
def tr():
    return xplane.Trace(DATA)


def _read(name, ctx):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"metric_{name.replace('.', '_')}", os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _ctx(tr, **kw):
    base = dict(trace=tr, chips=1, sizes={"vocab_size": 16384},
                peaks=counts.peaks("TPU v5 lite"), completions=[],
                window_s=tr.window_ns * 1e-9, prompt_len=512)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_window_spans_and_device(tr):
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    names = [n for n, _, _ in tr.spans]
    assert names.count("bench.step") == 5
    assert names.count("bench.collect") == 5
    assert tr.window_ns == pytest.approx(765_973_192)


def test_busy_is_the_union_of_operations_in_the_window(tr):
    dev = tr.devices[0]
    busy = tr.busy_ns(dev)
    assert 0 < busy < tr.window_ns
    assert busy == pytest.approx(5_289_596)
    # the union never exceeds the sum of what it covers
    ops = sum(e - s for _, s, e in dev.ops if tr.t0 <= s < tr.t1)
    assert busy <= ops + 1


def test_idle_share_reader(tr):
    idle = _read("idle_share", _ctx(tr))
    assert idle == pytest.approx(100 * (1 - 5_289_596 / 765_973_192))


def test_program_device_times(tr):
    runs = tr.module_runs(tr.devices[0], "jit_probe")
    assert len(runs) == 4            # the first ran before the window
    ms = _read("probe_ms.lat", _ctx(tr))
    assert ms == pytest.approx(sum(e - s for s, e in runs) / 4 * 1e-6)
    assert 0.09 < ms < 0.11
    assert _read("segment_ms", _ctx(tr)) is None   # no such program


def test_device_to_host_copies(tr):
    assert tr.d2h_bytes() == 5 * 268_435_456
    copy = xplane.overlap(tr.d2h(), tr.t0, tr.t1)
    collect = sum(e - s for n, s, e in tr.spans if n == "bench.collect")
    assert 0.6 * collect < copy <= collect


def test_step_idle_reader(tr):
    steps = [(s, e) for n, s, e in tr.spans if n == "bench.step"]
    ms = _read("step_idle_ms.lat", _ctx(tr))
    mean_step = sum(e - s for s, e in steps) / len(steps) * 1e-6
    assert 0 < ms < mean_step


def test_breakdown_lists_ops_and_gaps(tr):
    b = xplane.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) == 10
    names = [n for n, _ in b["device_ops"]]
    assert "jit_probe/convolution_tanh_fusion" in names
    secs = [v for _, v in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert b["idle_gaps"][0][0].startswith("bench.collect")
    assert any(n.endswith("device-to-host copy") for n, _ in b["idle_gaps"])


def test_union_overlap_and_gaps():
    merged = xplane.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert xplane.overlap(merged, 2, 6) == 2
    assert xplane.gaps(merged, 0, 10) == [(3, 5), (9, 10)]
    assert xplane.module_name("jit_run(1234)") == "jit_run"


def test_readout_reader_adds_program_time_and_copies(tr):
    # in the recorded trace the program that writes f32[8,512,16384] is a
    # plain add; with that width as the vocabulary it stands as a readout
    comp = [{"nfe": 9, "status": "ok"}] * 8
    ms = _read("readout_ms", _ctx(tr, completions=comp))
    runs = [(s, e) for m, s, e in tr.devices[0].modules
            if m == "jit_add" and tr.t0 <= s < tr.t1]
    copy = xplane.overlap(tr.d2h(), tr.t0, tr.t1)
    assert ms == pytest.approx(
        (sum(e - s for s, e in runs) + copy) * 1e-6 / 8)


def test_kernel_roofline_reader(tr):
    # the kernel ran outside any segment program here: nothing to read
    assert _read("hyper_step_roofline", _ctx(tr)) is None
