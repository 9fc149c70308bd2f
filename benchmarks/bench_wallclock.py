"""Wall-clock benchmarks: paper Fig. 4 (solver race) + the serving loop.

Two sections, one REAL clock (``time.perf_counter``):

1. ``fig4_rows`` — the paper's Fig. 4: wall-clock solve time of
   fixed-step methods vs dopri5 at iso-accuracy (each method runs the
   minimum K keeping accuracy loss vs dopri5 under 0.1%). CPU timings
   (documented); the paper's metric of record, NFE/MACs, is
   hardware-neutral and also reported.
2. ``serving_rows`` — the in-flight serving runtime head-to-head: the
   pipelined ``--overlap`` loop vs the synchronous loop replaying the
   SAME seeded Poisson traces, measured end-to-end under
   ``time.perf_counter``. Emits ``BENCH_wallclock.json`` (repo root)
   with four row kinds:

     * ``section="serving"`` — one row per (trace, loop): wall seconds
       (min + median over interleaved warm repeats), requests/s, ticks,
       mean per-tick wall-us, and the loops' output ``agreement``
       (uid-for-uid identical completions, checked on a cold replay).
     * ``section="mechanism"`` — the async-dispatch measurement the
       overlap design rests on: time for the segment cell's ``jit``
       call to RETURN (dispatch) vs time to actually finish (execute).
       Dispatch must be a small fraction of execute, or there is
       nothing for the host to overlap into. Measured in both donate
       modes — on the CPU client a donating call dispatches
       synchronously, the reason ``InflightScheduler``'s ``donate``
       auto-default is platform-aware.
     * ``section="predicted_vs_measured"`` — joins the measured mean
       per-tick wall-us against the ``RooflineOracle`` device-us price
       of the same (seg, slots) segment. The units differ on purpose
       (``wall_us`` measured on a host-CPU toy pool vs ``device_us``
       predicted for a qwen3_8b decode pool on accelerator HBM
       bandwidth) — the join validates the per-tick accounting
       plumbing and the scaling shape, not absolute calibration; rows
       carry both unit tags so downstream analysis can never sum them.
     * ``mode="verdict"`` — scoreboard: per-trace overlap speedups,
       ``overlap_wins_wallclock``, ``agreement_all``,
       ``async_dispatch_ok``, and ``host_cpus``. On a single-core host
       (CI containers) the two loops are work-conserving — wall time
       is total CPU work, which identical schedules make identical —
       so speedups sit at ~1.0 +/- scheduler noise and the verdict
       records that honestly; the overlap win needs ``host_cpus >= 2``
       so XLA's worker threads run under the host-side admit/retire.

Timing protocol (``serving_rows``): the cold replay per loop pays
compilation and pins agreement; timed repeats then re-replay the SAME
scheduler instance with a time-shifted copy of the trace (virtual
clocks are translation-invariant; a fresh scheduler would recompile its
jit cells). Repeats interleave the two loops in alternating order with
GC disabled, and the reported req/s uses the MIN wall time — on a
shared/noisy host the minimum is the closest observable to the
structural cost (same reasoning as ``timeit``).
"""
from __future__ import annotations

import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    accuracy_drop, eval_solver, fit_image_hypersolver, timed,
    train_image_node,
)
from repro.core import FixedGrid, get_tableau
from repro.data import synthetic_images
from repro.models.conv_node import mnist_integrator

OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_wallclock.json")

#: Serving-section budgets: (seeds, n_requests, d, slots, seg, rate,
#: timed repeats). "smoke" is the tier-1 variant — 2 tiny traces, small
#: pool, enough to pin agreement and schema but not to win a race.
SERVING_BUDGETS = {
    "smoke": ((3, 11), 12, 64, 4, 2, 0.4, 3),
    "small": ((3, 11, 21), 48, 256, 8, 4, 0.4, 9),
    "full": ((3, 11, 21), 96, 256, 8, 4, 0.4, 15),
}


def _min_K_for_accuracy(node, params, name, xt, gp, threshold=0.1,
                        K_grid=(1, 2, 4, 8, 16, 32)):
    for K in K_grid:
        out = eval_solver(node, params, name, K, xt,
                          gp=gp if name.startswith("hyper") else None)
        if accuracy_drop(node, params, out["zT"], out["z_ref"]) <= threshold:
            return K, out["nfe"]
    return K_grid[-1], out["nfe"]


def fig4_rows(budget: str = "small"):
    node, params = train_image_node()
    gp = fit_image_hypersolver(node, params, "euler", K=10)
    xt, _ = synthetic_images("mnist28", 32, seed=11)

    f = node.field(params, xt)
    z0 = node.hx_apply(params, xt)

    rows = []
    # dopri5 reference timing
    ref_fn = jax.jit(lambda z: node.reference_trajectory(
        params, xt, K=1, atol=1e-4, rtol=1e-4)[0][-1])
    t_ref, _ = timed(ref_fn, z0)
    rows.append({"bench": "wallclock_mnist", "solver": "dopri5", "K": "-",
                 "nfe": "adaptive", "ms": round(t_ref * 1e3, 2),
                 "speedup_vs_dopri5": 1.0})

    for name in ("euler", "hyper_euler", "midpoint", "rk4"):
        K, nfe = _min_K_for_accuracy(node, params, name, xt, gp)
        grid = FixedGrid.over(0.0, 1.0, K)
        if name.startswith("hyper"):
            integ = mnist_integrator(gp, xt, base="euler")
        else:
            integ = mnist_integrator(base=get_tableau(name))
        fn = jax.jit(lambda z, it=integ, gr=grid: it.solve(
            f, z, gr, return_traj=False))
        t, _ = timed(fn, z0)
        rows.append({"bench": "wallclock_mnist", "solver": name, "K": K,
                     "nfe": nfe, "ms": round(t * 1e3, 2),
                     "speedup_vs_dopri5": round(t_ref / t, 2)})
    return rows


# ------------------------------------------------------------- serving ----

def _shifted(trace, dt):
    from repro.launch.workload import Arrival
    return [Arrival(t=a.t + dt, x=a.x) for a in trace]


def _agreement(rep_a, rep_b) -> float:
    """Fraction of uid-matched completions identical across two replays:
    same K, nfe, and timestamps, bitwise-equal outputs. 1.0 = the
    pipelined loop is observationally the synchronous loop."""
    recs_a = {r.uid: r for r in rep_a.records}
    recs_b = {r.uid: r for r in rep_b.records}
    if set(recs_a) != set(recs_b):
        return 0.0
    same = 0
    for uid, ra in recs_a.items():
        rb = recs_b[uid]
        if (ra.K == rb.K and ra.nfe == rb.nfe
                and ra.t_submit == rb.t_submit
                and ra.t_admit == rb.t_admit
                and ra.t_done == rb.t_done
                and np.array_equal(np.asarray(ra.outputs),
                                   np.asarray(rb.outputs))):
            same += 1
    return same / max(len(recs_a), 1)


def _mechanism_row(d: int = 4096, slots: int = 8, seg: int = 8):
    """Measure async dispatch directly on the segment cell: wall time
    for the jit call to RETURN (dispatch) vs wall time for the retire
    meta to materialize (execute). The gap is the window the overlap
    loop fills with host-side admit/probe/retire work. Measured in both
    donate modes: ``donated_dispatch_us`` shows whether a DONATING call
    still returns before the segment finishes on this backend (the
    scheduler's ``donate`` auto-default keeps donation off on CPU —
    ``InflightScheduler.__init__``)."""
    from repro.launch.workload import heterogeneous_requests, toy_classifier

    m = toy_classifier("euler", d=d)
    xs = jnp.asarray(np.asarray(heterogeneous_requests(slots, d, seed=0)))
    k = jnp.zeros((slots,), jnp.int32)
    Ks = jnp.full((slots,), 64, jnp.int32)
    eps = jnp.full((slots,), 0.01, jnp.float32)
    times = {}
    for donate in (False, True):
        cell = m.integ.segment_cell(m.field_of, seg, donate=donate)
        params = m.params
        z = jnp.zeros((slots, d), jnp.float32)
        fs = jnp.zeros((slots, d), jnp.float32)
        z, fs, meta = cell(params, xs, z, k, Ks, eps, fs)   # compile
        np.array(meta)
        dispatch, execute = [], []
        for _ in range(11):
            t0 = time.perf_counter()
            z, fs, meta = cell(params, xs, z, k, Ks, eps, fs)
            t1 = time.perf_counter()
            np.array(meta)              # block until the segment finishes
            t2 = time.perf_counter()
            dispatch.append(t1 - t0)
            execute.append(t2 - t1)
        times[donate] = (float(np.median(dispatch) * 1e6),
                         float(np.median(execute) * 1e6))
    dispatch_us, block_us = times[False]
    execute_us = dispatch_us + block_us             # full segment wall
    donated_dispatch_us = times[True][0]
    return {"bench": "wallclock_serving", "section": "mechanism",
            "backend": jax.default_backend(),
            "d": d, "slots": slots, "seg": seg,
            "dispatch_us": round(dispatch_us, 1),
            "execute_us": round(execute_us, 1),
            "overlap_window_us": round(execute_us - dispatch_us, 1),
            "async_dispatch_ok": bool(dispatch_us < execute_us / 5),
            "donated_dispatch_us": round(donated_dispatch_us, 1),
            "donation_serializes_dispatch": bool(
                donated_dispatch_us > execute_us / 2),
            "time_unit": "wall_us"}


def serving_rows(budget: str = "small"):
    """The overlap-vs-sync wall-clock head-to-head (see module docstring
    for the protocol). Returns serving + mechanism + predicted-vs-
    measured + verdict rows; pure function of the budget and the host."""
    from repro.configs import get
    from repro.launch.engine import EngineConfig
    from repro.launch.oracle import WALLCLOCK_UNIT, RooflineOracle
    from repro.launch.scheduler import InflightScheduler
    from repro.launch.workload import (heterogeneous_requests,
                                       latency_stats, poisson_trace,
                                       replay_scheduler, toy_classifier)

    seeds, n, d, slots, seg, rate, reps = SERVING_BUDGETS[budget]
    host_cpus = os.cpu_count() or 1
    rows = []
    speedups = {}
    agreements = {}
    oracle = RooflineOracle(get("qwen3_8b"), ctx=4096)
    predicted_us = oracle.segment_cost((d,), seg, slots, stages=1)

    for seed in seeds:
        trace_name = f"poisson_seed{seed}"
        ecfg = EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=8,
                            solver="euler", fused=True)
        xs = heterogeneous_requests(n, d, seed=seed)
        trace = poisson_trace(xs, rate=rate, seed=seed + 100)

        # cold replays: pay compilation, pin uid-for-uid agreement
        scheds, cold = {}, {}
        for loop, overlap in (("sync", False), ("overlap", True)):
            s = InflightScheduler(toy_classifier("euler", d=d), ecfg,
                                  slots=slots, seg=seg, overlap=overlap)
            cold[loop] = replay_scheduler(s, trace)
            scheds[loop] = s
        agreement = _agreement(cold["sync"], cold["overlap"])
        agreements[trace_name] = agreement

        # warm timed repeats: interleaved, alternating order, GC off
        times = {"sync": [], "overlap": []}
        ticks = {"sync": 0, "overlap": 0}
        gc.disable()
        try:
            for rep in range(reps):
                order = (("sync", "overlap") if rep % 2 == 0
                         else ("overlap", "sync"))
                for loop in order:
                    s = scheds[loop]
                    tr = _shifted(trace, s.now + 1.0)
                    t_before = s.ticks
                    t0 = time.perf_counter()
                    replay_scheduler(s, tr)
                    times[loop].append(time.perf_counter() - t0)
                    ticks[loop] = s.ticks - t_before
        finally:
            gc.enable()

        stats = latency_stats(cold["sync"])
        for loop in ("sync", "overlap"):
            wall_min = min(times[loop])
            wall_med = float(np.median(times[loop]))
            rows.append({
                "bench": "wallclock_serving", "section": "serving",
                "loop": loop, "trace": trace_name, "requests": n,
                "d": d, "slots": slots, "seg": seg, "rate": rate,
                "reps": reps,
                "wall_s_min": round(wall_min, 4),
                "wall_s_median": round(wall_med, 4),
                "req_per_s": round(n / wall_min, 2),
                "ticks": ticks[loop],
                "mean_tick_wall_us": round(
                    wall_min * 1e6 / max(ticks[loop], 1), 1),
                "time_unit": WALLCLOCK_UNIT,
                "agreement": agreement,
                "mean_nfe": stats["mean_nfe"],
                "host_cpus": host_cpus,
            })
        sync_row, overlap_row = rows[-2], rows[-1]
        speedups[trace_name] = round(
            overlap_row["req_per_s"] / sync_row["req_per_s"], 3)
        rows.append({
            "bench": "wallclock_serving",
            "section": "predicted_vs_measured", "trace": trace_name,
            "seg": seg, "slots": slots,
            "predicted_device_us_per_segment": round(predicted_us, 1),
            "predicted_unit": oracle.unit,
            "measured_wall_us_per_tick":
                overlap_row["mean_tick_wall_us"],
            "measured_unit": WALLCLOCK_UNIT,
            "measured_over_predicted": round(
                overlap_row["mean_tick_wall_us"] / predicted_us, 3),
            "note": ("predicted prices a qwen3_8b decode pool on "
                     "accelerator HBM; measured is a toy host-CPU pool "
                     "— join validates per-tick accounting, not "
                     "absolute calibration"),
        })

    mech = _mechanism_row()
    rows.append(mech)
    rows.append({
        "bench": "wallclock_serving", "mode": "verdict",
        "overlap_wins_wallclock": bool(
            all(s >= 1.0 for s in speedups.values())),
        "overlap_speedups": speedups,
        "agreement_all": float(min(agreements.values())),
        "async_dispatch_ok": mech["async_dispatch_ok"],
        "host_cpus": host_cpus,
        "note": ("identical schedules make the two loops work-"
                 "conserving: on a 1-core host wall time is total CPU "
                 "work and speedups sit at ~1.0 +/- noise; the overlap "
                 "win requires host_cpus >= 2 so the XLA worker runs "
                 "under host-side admit/probe/retire (the mechanism "
                 "row measures that window directly)"),
    })
    return rows


def main(budget: str = "small"):
    import json
    if budget == "smoke":
        return serving_rows("smoke")    # tier-1: no training, no JSON
    rows = fig4_rows(budget) + serving_rows(budget)
    with open(OUT_PATH, "w") as fh:
        json.dump([r for r in rows if r["bench"] == "wallclock_serving"],
                  fh, indent=1)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", default="small",
                    choices=sorted(SERVING_BUDGETS))
    for r in main(budget=ap.parse_args().budget):
        print(r)
