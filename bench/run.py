"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's server from ``--seed`` (weights on the device, then the
program's depth model and in-flight scheduler), warms every shape the
window uses, serves the cell's traffic for ``--seconds`` on the wall
clock (a backlog's window closes at the first completions after that),
and checks a seeded sample of what was served against the plain
reference (``check.py``). The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics read
from a profiler trace of part of the window), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number
compared, with its limit. The same numbers end standard error.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero. ``--rehearse`` runs the cell at cut widths on
the CPU (Pallas in interpret mode) to find faults before a chip run; its
last line is ``{"rehearsal": {...}}``, never a result.

Which metrics a cell reports is read from ``BENCHMARK.json`` beside
this directory; a per-layer metric ``<name>`` is read by
``metrics/<name>.py``.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import types
from typing import Dict

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _program() -> None:
    """Put the system under test, ``src/repro`` of this checkout, on the
    path."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no program at {src}/repro; run from the "
                         "root of a checkout")
    if src not in sys.path:
        sys.path.insert(0, src)


def _declared(cell: str, bench_dir: str):
    """(end-to-end, per-layer) metrics of ``cell`` as ``BENCHMARK.json``
    beside ``bench_dir`` declares them, name -> unit."""
    with open(os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json")) as f:
        b = json.load(f)

    def mine(ms):
        return {m["name"]: m["unit"] for m in ms
                if cell in m.get("workloads", [cell])}

    return mine(b["end_to_end"]), mine(b["per_layer"])


def _reader(name: str, bench_dir: str):
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def use_compile_cache() -> str:
    """JAX's persistent compilation cache, so that only a cell's first run
    in a checkout compiles: ``JAX_COMPILATION_CACHE_DIR`` where it is set
    (JAX's own setting, left alone), else the fixed ``.jax_cache/bench``
    inside the checkout. Every program is kept, however small, and none is
    evicted: an evicting cache breaks on entries that another writer left
    without their access-time files."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache", "bench")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def per_layer(names: Dict[str, str], w, tr, sizes, cell, peak,
              bench_dir: str) -> Dict[str, dict]:
    comp = [{"nfe": n, "status": s} for s, n in w.traced["done"]]
    ctx = types.SimpleNamespace(
        trace=tr, counters=w.counters, completions=comp, sizes=sizes,
        layout=cell.layout,
        prompt_len=int(cell.traffic["prompt_len"]), peaks=peak,
        chips=cell.chips, window_s=tr.window_ns * 1e-9)
    out = {}
    for name, unit in names.items():
        value = _reader(name, bench_dir)(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
        else:
            log(f"per-layer {name}: nothing in the trace to read")
    return out


def run(name: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, bench_dir: str = BENCH,
        control: bool = False) -> dict:
    """One run of cell ``name``; returns the result object (and, with
    ``control``, the control's numbers under ``control``)."""
    t_setup = time.perf_counter()
    _program()
    import jax

    import check
    import serve
    import spec
    import xplane

    cell = spec.load_cell(name, bench_dir)
    sizes = spec.model_sizes(cell, rehearse)
    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < cell.chips:
        raise SystemExit(f"bench: {name} needs {cell.chips} chips, JAX sees "
                         f"{len(devs)}")
    devs = devs[:cell.chips]
    compiles = serve.compile_counter()
    cfg = spec.arch_config(sizes, cell.layout)
    log(f"bench: cell={name} seed={seed} seconds={seconds} trace={int(trace)}"
        f" platform={devs[0].platform} kind={devs[0].device_kind!r} "
        f"chips={len(devs)} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab} dtype={cfg.dtype}")
    t_build = time.perf_counter()
    params, gw, sched = serve.build(cell, cfg, seed)
    jax.block_until_ready(params)
    t_warm = time.perf_counter()
    serve.warm(sched, cell.traffic, cfg.vocab, seed)
    setup_s = time.perf_counter() - t_setup
    log(f"bench: setup_s={setup_s!r} (start {t_build - t_setup:.2f} s, "
        f"weights and server {t_warm - t_build:.2f} s, warm-up "
        f"{t_setup + setup_s - t_warm:.2f} s) compiles_in_setup={compiles.n}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        w = serve.run_window(sched, cell, cfg.vocab, seed, seconds, compiles,
                             trace_dir)
        log(f"bench: compiles_in_window={w.compiles} (expected 0)")
        log("bench: host seconds in the window: " + " ".join(
            f"{k}={v:.3f}" for k, v in w.host_s.items())
            + f" of {w.seconds:.3f}, {len(w.queue)} ticks; longest "
            + " ".join(f"{k}={v:.3f}" for k, v in w.longest_s.items()))
        log("bench: requests pending at 25/50/75/100% of the window: "
            + " ".join(str(serve.pending_at(w, f)) for f in (.25, .5, .75, 1)))
        if w.late_s:
            log(f"bench: generator late by mean {sum(w.late_s) / len(w.late_s)!r}"
                f" s, max {max(w.late_s)!r} s over {len(w.late_s)} arrivals")
        e2e = serve.end_to_end(w, cell.traffic)
        e2e["setup_s"] = setup_s
        log("bench: end to end " + " ".join(f"{k}={v!r}" for k, v in e2e.items()))
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": serve.memory_peak()}
        del sched
        gc.collect()
        tr = xplane.load(trace_dir) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    failed = sum(w.status.get(u) != "ok" for u in w.in_window)
    t_check = time.perf_counter()
    numbers = check.score(cell.layout, params, gw, sizes, cell.server,
                          w.samples, control=control)
    log(f"bench: reference check of {len(w.samples)} requests took "
        f"{time.perf_counter() - t_check:.2f} s")
    numbers["not_ok"] = float(failed)
    limits = cell.check["limits"]
    result = {"correct": check.verdict(numbers, limits),
              "attempted": len(w.in_window), "failed": failed}
    e2e_units, layer_units = _declared(name, bench_dir)
    if not trace:
        result["metrics"] = {k: {"value": e2e[k], "unit": u}
                             for k, u in e2e_units.items()}
    elif tr is None or not tr.devices:
        log("bench: the trace holds no TPU device; no per-layer metric")
        result["metrics"] = {}
    else:
        import counts

        peak = counts.peaks(devs[0].device_kind)
        result["metrics"] = per_layer(layer_units, w, tr, sizes, cell, peak,
                                      bench_dir)
        busy = [tr.busy_ns(d) for d in tr.devices[:cell.chips]]
        for d, b in zip(tr.devices, busy):
            log(f"bench: {d.name} busy {b * 1e-9!r} s of "
                f"{tr.window_ns * 1e-9!r} s")
        log(f"bench: device-to-host bytes in the traced span "
            f"{tr.d2h_bytes()}")
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = tr.window_ns * 1e-9
        result["breakdown"] = xplane.breakdown(tr)
    result["device"] = device
    if control:
        result["control"] = {k: v for k, v in numbers.items()
                             if k.startswith("control_")}
    result["checks"] = {k: {"value": numbers[k], "limit": float(limits[k])}
                        for k in check.NAMES}
    for line in check.lines(numbers, limits):
        log(line)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="cut widths, run on the CPU, print no result")
    args = ap.parse_args(argv)
    if args.rehearse:
        import spec

        os.environ["JAX_PLATFORMS"] = "cpu"
        chips = spec.load_cell(args.workload).chips
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}")
    else:
        use_compile_cache()
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 rehearse=args.rehearse)
    if args.rehearse:
        print(json.dumps({"rehearsal": result}), flush=True)
    else:
        print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
