"""In-flight scheduler vs drain-the-queue engine, identical arrival traces.

    PYTHONPATH=src python benchmarks/bench_scheduler.py [--budget small]

The serving-latency head-to-head the ROADMAP's async-serving item calls
for: seeded Poisson and bursty arrival traces over a heterogeneous
difficulty mix replay through BOTH loops (launch/workload.py drivers) on
the same virtual clock (sequential vector-field evaluations):

  * engine   — ``MultiRateEngine``: drain everything queued, probe, pack
    by bucket, solve each batch to completion (launch/engine.py);
  * inflight — ``InflightScheduler``: slot pool over the resumable
    segment solve; finished slots retire and refill between segments
    (launch/scheduler.py).

Both use the SAME controller, buckets, and solver, so every request gets
the same K and numerically matching outputs — agreement against the
fine-mesh reference is equal BY CONSTRUCTION (asserted per trace), and
the comparison isolates scheduling: queue wait, p50/p99 latency,
throughput, slot occupancy, masked-step waste.

A second section (``sharded_rows``, run under 4 forced host devices in a
subprocess) pits the single-device slot pool against the slot-axis-
sharded pool (``InflightScheduler(mesh=)``) on hot Poisson traces past
one pool's capacity: same policy, same agreement, n-fold the slots at
the same sequential cost per segment.

A third section (``oracle_rows``) replays the same workload on the
roofline cost oracle (``launch/oracle.py``): completions priced in
predicted device-us of a qwen3_8b decode cell instead of sequential
field evals, plus the scheduler-knob autotune verdicts
(``launch/autotune.py``, persisted to ``artifacts/tuned/``). Every row
carries ``cost_unit`` so the two clocks are never cross-compared.

The JSON written to BENCH_scheduler.json carries one row per
(loop, trace, config) plus a ``verdict`` row: ``inflight_wins_p99`` is
True when the scheduler beats the engine's p99 latency at equal agreement
on at least one seeded Poisson trace, and ``sharded_pool_ok`` is True
when the multi-device pool holds throughput at-or-above the single-device
pool at equal agreement on every hot trace — the tracked serving
scoreboards (benchmarks/run.py --check enforces both).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
if __name__ == "__main__":  # runnable as a script from anywhere
    sys.path.insert(0, REPO_ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FixedGrid, Integrator
from repro.launch.engine import DepthModel, EngineConfig, MultiRateEngine
from repro.launch.scheduler import InflightScheduler
from repro.launch.workload import (
    bursty_trace, heterogeneous_requests, latency_stats, poisson_trace,
    replay_engine, replay_scheduler, toy_classifier,
)

OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_scheduler.json")

D_FEAT = 32          # toy_classifier's input width (launch/workload.py)
N_CLASSES = 10


def reference_argmax(model: DepthModel, xs: np.ndarray) -> np.ndarray:
    """Fine-mesh ground truth (K=64 base-tableau solve, no correction)."""
    integ = Integrator(tableau=model.integ.tableau)
    x, p = jnp.asarray(xs), model.params
    zT = integ.solve(model.field_of(p, x), model.embed(p, x),
                     FixedGrid.over(0.0, 1.0, 64), return_traj=False)
    return np.asarray(jnp.argmax(model.readout(p, x, zT), -1))


def _agreement(records, ref_top) -> float:
    by_uid = sorted(records, key=lambda r: r.uid)  # uid == arrival order
    top = np.asarray([np.argmax(r.outputs, -1) for r in by_uid])
    return float(np.mean(top == ref_top))


def run_trace(trace, xs, ecfg, solver, slots, seg, workload):
    """One trace through both loops; returns the two stat rows."""
    ref_top = reference_argmax(toy_classifier(solver), xs)

    eng = MultiRateEngine(toy_classifier(solver), ecfg)
    rep_e = replay_engine(eng, trace)
    row_e = latency_stats(rep_e)
    row_e.update(bench="scheduler", mode="engine", trace=workload,
                 solver=solver, max_batch=ecfg.max_batch,
                 agreement=round(_agreement(rep_e.records, ref_top), 4))

    sched = InflightScheduler(toy_classifier(solver), ecfg, slots=slots,
                              seg=seg)
    rep_s = replay_scheduler(sched, trace)
    row_s = latency_stats(rep_s)   # occupancy rides in the shared summary
    row_s.update(bench="scheduler", mode="inflight", trace=workload,
                 solver=solver, slots=slots, seg=seg, devices=1,
                 agreement=round(_agreement(rep_s.records, ref_top), 4))

    # equal-K, numerically matching outputs: agreement must tie exactly
    assert row_e["agreement"] == row_s["agreement"], (row_e, row_s)
    return row_e, row_s


# ------------------------------------------------- multi-device section ----

def sharded_rows(budget: str = "small", n_devices: int = 4):
    """Single- vs multi-device slot pool on identical hot Poisson traces.

    Requires ``n_devices`` visible jax devices — ``main()`` runs this in a
    subprocess with a forced host device count (the same pattern as the
    debug-mesh tests), never in the importing process. The comparison the
    ROADMAP's slot-sharding item calls for: a pool capped at what one chip
    holds (``slots_per_dev``) vs the sharded pool holding
    ``slots_per_dev * n_devices`` rows at the SAME sequential cost per
    segment (the slot axis is the hardware-parallel one) — under load the
    single pool queues and the sharded pool keeps admitting."""
    import jax

    from repro.launch.mesh import make_serving_mesh

    assert jax.device_count() >= n_devices, (
        f"sharded_rows needs {n_devices} devices, found "
        f"{jax.device_count()} — run under "
        "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    n = {"tiny": 24, "small": 64, "full": 192}.get(budget, 64)
    mesh = make_serving_mesh(n_devices)
    ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=8,
                        solver="euler", fused=True)
    slots_per_dev, seg = 8, 2
    pairs = []
    # hot Poisson traces: rate chosen past the single pool's capacity
    # (~slots_per_dev * seg steps per stages*seg cost, discounted by
    # probes + masked waste) so queueing separates the two pools
    for seed in (3, 11):
        xs = heterogeneous_requests(n, D_FEAT, seed=seed)
        trace = poisson_trace(xs, rate=1.5, seed=seed + 100)
        ref_top = reference_argmax(toy_classifier("euler"), xs)
        pair = []
        for devices, m in ((1, None), (n_devices, mesh)):
            sched = InflightScheduler(
                toy_classifier("euler"), ecfg,
                slots=slots_per_dev * devices, seg=seg, mesh=m)
            rep = replay_scheduler(sched, trace)
            row = latency_stats(rep)
            row.update(bench="scheduler", mode="inflight",
                       trace=f"poisson_hot_seed{seed}", solver="euler",
                       slots=slots_per_dev * devices, seg=seg,
                       devices=devices, backend=jax.default_backend(),
                       agreement=round(_agreement(rep.records, ref_top),
                                       4))
            pair.append(row)
        # same controller + buckets through both pools: equal agreement
        # is BY CONSTRUCTION, so the comparison isolates pool capacity
        assert pair[0]["agreement"] == pair[1]["agreement"], pair
        pairs.append(pair)
    return pairs   # explicit (single, sharded) pairs — never re-zipped


# ------------------------------------------------ roofline-oracle section ----

def oracle_rows(budget: str = "small"):
    """The roofline-oracle clock section: the same toy workload replayed
    through BOTH loops with completions stamped in predicted device-us
    (``launch/oracle.py::RooflineOracle`` pricing a qwen3_8b decode
    cell), plus the per-cell scheduler-knob autotune verdicts
    (``launch/autotune.py``) whose chosen configs persist to
    ``artifacts/tuned/`` — ``benchmarks/run.py --check`` fails if those
    files drift from the verdict rows here."""
    from repro.configs import get
    from repro.launch.autotune import TUNE_CELLS, autotune_cell, save_tuned
    from repro.launch.oracle import RooflineOracle

    n = {"tiny": 16, "small": 48, "full": 128}.get(budget, 48)
    solver = "euler"
    ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=8,
                        solver=solver, fused=True)
    slots, seg = 8, 2
    oracle = RooflineOracle(get("qwen3_8b"), ctx=4096)
    # same relative load as the sequential poisson_seed3 trace: the rate
    # converts from per-field-eval to per-device-us via the pool's step
    # price, so the two sections stress the loops identically and only
    # the clock differs
    rate = 0.25 / oracle.step_time(slots)
    xs = heterogeneous_requests(n, D_FEAT, seed=3)
    trace = poisson_trace(xs, rate=rate, seed=103)
    ref_top = reference_argmax(toy_classifier(solver), xs)

    eng = MultiRateEngine(toy_classifier(solver), ecfg, oracle=oracle)
    rep_e = replay_engine(eng, trace)
    row_e = latency_stats(rep_e)
    row_e.update(bench="scheduler", mode="engine", trace="poisson_oracle",
                 clock="roofline", solver=solver, max_batch=ecfg.max_batch,
                 agreement=round(_agreement(rep_e.records, ref_top), 4))

    sched = InflightScheduler(toy_classifier(solver), ecfg, slots=slots,
                              seg=seg, oracle=oracle)
    rep_s = replay_scheduler(sched, trace)
    row_s = latency_stats(rep_s)
    row_s.update(bench="scheduler", mode="inflight", trace="poisson_oracle",
                 clock="roofline", solver=solver, slots=slots, seg=seg,
                 devices=1,
                 agreement=round(_agreement(rep_s.records, ref_top), 4))
    assert row_e["agreement"] == row_s["agreement"], (row_e, row_s)

    # knob autotune per serving cell; the full hillclimb log lives in
    # artifacts/tuned/<cell>.json, the BENCH row carries the verdict
    tuner_rows = []
    for spec in TUNE_CELLS:
        res = autotune_cell(spec, budget=budget)
        save_tuned(res, os.path.join(REPO_ROOT, "artifacts", "tuned"))
        tuner_rows.append({k: v for k, v in res.items() if k != "log"})
    return [row_e, row_s] + tuner_rows


def _start_sharded_section(budget: str):
    """Launch ``sharded_rows`` under a forced 4-device CPU host in a
    subprocess (jax device topology is frozen at first init, so the
    importing process cannot grow devices itself). Started BEFORE the
    in-process trace loop — the two share nothing — and joined by
    ``_join_sharded_section``."""
    script = (
        "import os, json, sys\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        f"sys.path.insert(0, {REPO_ROOT!r})\n"
        "from benchmarks.bench_scheduler import sharded_rows\n"
        f"print('SHARDED_ROWS=' + json.dumps(sharded_rows({budget!r}), "
        "default=str))\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
    env.pop("XLA_FLAGS", None)
    # the forced devices are CPU devices; a child that asked for the
    # accelerator would contend with this process, which holds it
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen([sys.executable, "-c", script], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            cwd=REPO_ROOT)


def _join_sharded_section(proc):
    stdout, stderr = proc.communicate(timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError("sharded bench subprocess failed:\n"
                           + (stdout + stderr)[-4000:])
    line = [l for l in stdout.splitlines()
            if l.startswith("SHARDED_ROWS=")][-1]
    return json.loads(line[len("SHARDED_ROWS="):])


def main(budget: str = "small", out_path: str = OUT_PATH):
    # the multi-device section (4 forced host devices, subprocess) shares
    # nothing with the in-process loops — overlap it with them
    sh_proc = _start_sharded_section(budget)
    n = {"tiny": 32, "small": 96, "full": 256}.get(budget, 96)
    solver = "euler"
    ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=8,
                        solver=solver, fused=True)
    slots, seg = 8, 2

    pairs = []
    # Poisson at moderate load (the regime where drain latency compounds):
    # rate is in requests per sequential field eval; mean service ~9 steps
    # over 8 parallel slots puts capacity near 0.45 req/unit.
    for seed in (3, 11):
        xs = heterogeneous_requests(n, D_FEAT, seed=seed)
        trace = poisson_trace(xs, rate=0.25, seed=seed + 100)
        pairs.append(run_trace(trace, xs, ecfg, solver, slots, seg,
                               f"poisson_seed{seed}"))

    # bursty arrivals: bursts of 2x the slot pool, spaced one mean
    # service-time apart — the drain loop's worst case
    xs = heterogeneous_requests(n, D_FEAT, seed=5)
    trace = bursty_trace(xs, burst=16, gap=60.0, seed=7)
    pairs.append(run_trace(trace, xs, ecfg, solver, slots, seg, "bursty"))

    # hypersolver serving config: residual controller (free probe) through
    # both loops — the paper's correction survives in-flight batching
    hyper_ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=8,
                              solver="hyper_euler", fused=True)
    xs = heterogeneous_requests(n, D_FEAT, seed=9)
    trace = poisson_trace(xs, rate=0.25, seed=13)
    pairs.append(run_trace(trace, xs, hyper_ecfg, "hyper_euler", slots,
                           seg, "poisson_hyper"))

    # roofline-oracle clock section + scheduler-knob autotune verdicts
    o_rows = oracle_rows(budget)

    # multi-device slot pool vs one chip, identical hot traces (4 forced
    # host devices in a subprocess — see sharded_rows)
    sh_pairs = _join_sharded_section(sh_proc)

    # verdict: does in-flight beat drain p99 at equal agreement on some
    # seeded Poisson trace? (explicit pairs — no positional row coupling)
    wins = []
    for row_e, row_s in pairs:
        if not row_s["trace"].startswith("poisson"):
            continue
        if (row_s["agreement"] >= row_e["agreement"]
                and row_s["p99_latency"] < row_e["p99_latency"]):
            wins.append({
                "trace": row_s["trace"], "solver": row_s["solver"],
                "p99_engine": row_e["p99_latency"],
                "p99_inflight": row_s["p99_latency"],
                "agreement": row_s["agreement"],
            })
    # sharded verdict: the multi-device pool must keep throughput at or
    # above the single-device pool at equal agreement on EVERY hot trace
    sh_wins, sh_ok = [], True
    for single, multi in sh_pairs:
        ok = (multi["agreement"] >= single["agreement"]
              and multi["throughput"] >= single["throughput"])
        sh_ok = sh_ok and ok
        sh_wins.append({
            "trace": multi["trace"], "devices": multi["devices"],
            "throughput_single": single["throughput"],
            "throughput_sharded": multi["throughput"],
            "p99_single": single["p99_latency"],
            "p99_sharded": multi["p99_latency"],
            "agreement": multi["agreement"], "ok": ok,
        })
    rows = [r for pair in pairs for r in pair] \
        + o_rows \
        + [r for pair in sh_pairs for r in pair]
    rows.append({
        "bench": "scheduler", "mode": "verdict",
        "inflight_wins_p99": bool(wins), "witnesses": wins[:4],
        "sharded_pool_ok": bool(sh_ok), "sharded_witnesses": sh_wins[:4],
    })
    with open(out_path, "w") as fh:
        json.dump(rows, fh, indent=1, default=str)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", default="small",
                    choices=["tiny", "small", "full"])
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    for r in main(args.budget, args.out):
        print(r)
