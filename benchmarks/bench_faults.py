"""Chaos harness for the hardened serving loops (PR 8).

    PYTHONPATH=src python benchmarks/bench_faults.py [--budget small]

Replays seeded arrival traces through BOTH serving loops — the drain
``MultiRateEngine`` and the in-flight ``InflightScheduler`` (sync and
``overlap=True``) — under seeded fault injection
(``distributed/fault.py::FaultInjector``) and overload, and writes
BENCH_faults.json with one row per (loop, fault mix):

  * **zero-hang** — every submitted uid reaches a terminal record,
    exactly once, under every mix (the hard liveness contract);
  * **status accounting** — the terminal-status histogram
    (``ok | retried | diverged | deadline | shed``) sums to the
    submitted count;
  * **completion rate + p99** — p99 latency computed over the requests
    that produced real outputs (``ok``/``retried``), never flattered by
    shed or evicted entries;
  * **fault-free parity** — a DISARMED injector (all rates zero) leaves
    both loops bitwise identical to running with no injector at all
    (uid-for-uid: outputs, nfe, clock stamps, status), sync and overlap,
    single-device and 4-device-mesh (subprocess, forced host devices) —
    the fault path costs nothing when nothing is injected.

Fault mixes: transient NaN poisoning (exercises the bounded retry
ladder -> ``retried``), persistent NaN (``diverged`` best-effort),
dropped retire flags (lost completion signals; re-drawn per segment so
p < 1 still terminates), virtual stragglers + per-request deadlines
(``deadline`` evictions), and queue overload under each policy
(``shed`` / ``degrade`` / ``block``).

The verdict row is the tracked scoreboard: ``zero_hang_all``,
``fault_free_parity``, ``status_accounting_ok``, ``overlap_parity_all``
(sync and overlap see identical fault schedules — every decision hashes
(seed, site, uid-or-tick), never call order). ``benchmarks/run.py
--check`` enforces all four.
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

import numpy as np

from repro.distributed.fault import FaultInjector
from repro.launch.engine import EngineConfig, MultiRateEngine
from repro.launch.scheduler import InflightScheduler
from repro.launch.workload import (
    bursty_trace, heterogeneous_requests, latency_stats, ok_records,
    poisson_trace, replay_engine, replay_scheduler, status_counts,
    toy_classifier,
)

OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_faults.json")

D_FEAT = 32
SLOTS, SEG = 8, 2


def _ecfg():
    return EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3, max_batch=8,
                        solver="euler", fused=True)


def _sched(inj=None, overlap=False, mesh=None, **hard):
    return InflightScheduler(toy_classifier("euler"), _ecfg(), slots=SLOTS,
                             seg=SEG, overlap=overlap, mesh=mesh,
                             fault_injector=inj, **hard)


def _engine(inj=None, **hard):
    return MultiRateEngine(toy_classifier("euler"), _ecfg(),
                           fault_injector=inj, **hard)


def records_bitwise_equal(rep_a, rep_b) -> bool:
    """uid-for-uid bitwise comparison of two replays: outputs, nfe, K,
    clock stamps, status. NaN outputs compare equal positionally (a
    diverged best-effort readout must still be deterministic)."""
    a = {r.uid: r for r in rep_a.records}
    b = {r.uid: r for r in rep_b.records}
    if set(a) != set(b):
        return False
    for u, ra in a.items():
        rb = b[u]
        if (ra.t_submit, ra.t_admit, ra.t_done, ra.K, ra.nfe,
                ra.status) != (rb.t_submit, rb.t_admit, rb.t_done, rb.K,
                               rb.nfe, rb.status):
            return False
        if (ra.outputs is None) != (rb.outputs is None):
            return False
        if ra.outputs is not None and not np.array_equal(
                ra.outputs, rb.outputs, equal_nan=True):
            return False
    return True


def fault_row(rep, n_submitted: int, mode: str, mix: str,
              devices: int = 1) -> dict:
    """One (loop, mix) accounting row: latency stats over the served
    requests + the liveness/status ledgers over ALL of them."""
    uids = [r.uid for r in rep.records]
    counts = status_counts(rep)
    row = latency_stats(ok_records(rep))
    row.update(
        bench="faults", mode=mode, mix=mix, devices=devices,
        submitted=n_submitted,
        zero_hang=bool(len(uids) == n_submitted
                       and len(set(uids)) == n_submitted),
        status_ok=bool(sum(counts.values()) == n_submitted),
        completion_rate=round(
            (counts["ok"] + counts["retried"]) / max(n_submitted, 1), 4),
        **{f"n_{k}": v for k, v in counts.items()})
    return row


# ----------------------------------------------------------- fault mixes ----

def mix_rows(budget: str = "small"):
    """Every fault mix through engine + in-flight sync + in-flight
    overlap. Returns (rows, overlap_parity_all): parity asserts the sync
    and overlap loops saw identical fault schedules AND resolved them to
    identical terminal records."""
    n = {"tiny": 24, "small": 48, "full": 128}.get(budget, 48)
    xs = heterogeneous_requests(n, D_FEAT, seed=3)
    base = poisson_trace(xs, rate=0.25, seed=103)
    dl_trace = poisson_trace(xs, rate=0.25, seed=103, deadline_slack=60.0)
    burst = bursty_trace(xs, burst=SLOTS * 3, gap=30.0, seed=7)

    mixes = [
        ("clean", base, FaultInjector(), {}),
        ("nan_transient", base,
         FaultInjector(seed=1, nan_uid_frac=0.25, nan_transient=True), {}),
        ("nan_persistent", base,
         FaultInjector(seed=1, nan_uid_frac=0.25, nan_transient=False), {}),
        ("drop_flags", base, FaultInjector(seed=2, drop_flag_p=0.5), {}),
        ("straggle_deadline", dl_trace,
         FaultInjector(seed=5, straggle_tick_frac=0.4, straggle_factor=8.0),
         {}),
        ("overload_shed", burst, None,
         {"queue_cap": SLOTS, "overload_policy": "shed"}),
        ("overload_degrade", burst, None,
         {"queue_cap": SLOTS, "overload_policy": "degrade"}),
        ("overload_block", burst, None,
         {"queue_cap": SLOTS, "overload_policy": "block"}),
    ]
    rows = []
    overlap_parity = True
    for mix, trace, inj, hard in mixes:
        rep_e = replay_engine(_engine(inj, **hard), trace)
        rows.append(fault_row(rep_e, n, "engine", mix))
        rep_s = replay_scheduler(_sched(inj, **hard), trace)
        rows.append(fault_row(rep_s, n, "inflight", mix))
        rep_o = replay_scheduler(_sched(inj, overlap=True, **hard), trace)
        rows.append(fault_row(rep_o, n, "inflight_overlap", mix))
        overlap_parity = overlap_parity \
            and records_bitwise_equal(rep_s, rep_o)
    return rows, overlap_parity


# ---------------------------------------------------- fault-free parity ----

def parity_rows(budget: str = "small", mesh=None, devices: int = 1):
    """ACCEPTANCE: on a fault-free trace, the hardened loops with a
    DISARMED injector are bitwise identical to the loops with no
    injector wired at all — uid for uid, both loop variants. Returns
    (rows, all_parity_ok)."""
    n = {"tiny": 24, "small": 48, "full": 128}.get(budget, 48)
    xs = heterogeneous_requests(n, D_FEAT, seed=9)
    trace = poisson_trace(xs, rate=0.25, seed=113)
    disarmed = FaultInjector()   # every rate zero: armed code, no faults

    checks = []
    rep_none = replay_scheduler(_sched(None, mesh=mesh), trace)
    rep_dis = replay_scheduler(_sched(disarmed, mesh=mesh), trace)
    checks.append(("inflight", records_bitwise_equal(rep_none, rep_dis)))
    rep_none_o = replay_scheduler(
        _sched(None, overlap=True, mesh=mesh), trace)
    rep_dis_o = replay_scheduler(
        _sched(disarmed, overlap=True, mesh=mesh), trace)
    checks.append(("inflight_overlap",
                   records_bitwise_equal(rep_none_o, rep_dis_o)))
    checks.append(("sync_vs_overlap",
                   records_bitwise_equal(rep_none, rep_none_o)))
    if mesh is None:
        rep_e_none = replay_engine(_engine(None), trace)
        rep_e_dis = replay_engine(_engine(disarmed), trace)
        checks.append(("engine",
                       records_bitwise_equal(rep_e_none, rep_e_dis)))
    rows = [{"bench": "faults", "mode": loop, "mix": "fault_free_parity",
             "devices": devices, "submitted": n, "parity": bool(ok)}
            for loop, ok in checks]
    return rows, all(ok for _, ok in checks)


# ------------------------------------------------- multi-device section ----

def sharded_chaos_rows(budget: str = "small", n_devices: int = 4):
    """The same contracts on the slot-axis-sharded pool: fault-free
    parity (disarmed == absent, sync == overlap) plus zero-hang under
    the NaN quarantine mix, with the pool sharded over ``n_devices``
    forced host devices. Run in a subprocess by ``main()`` — jax device
    topology is frozen at first init."""
    import jax

    from repro.launch.mesh import make_serving_mesh

    assert jax.device_count() >= n_devices, (
        f"sharded_chaos_rows needs {n_devices} devices, found "
        f"{jax.device_count()} — run under "
        "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    mesh = make_serving_mesh(n_devices)
    rows, parity_ok = parity_rows(budget, mesh=mesh, devices=n_devices)

    n = {"tiny": 24, "small": 48, "full": 128}.get(budget, 48)
    xs = heterogeneous_requests(n, D_FEAT, seed=3)
    trace = poisson_trace(xs, rate=0.25, seed=103)
    inj = FaultInjector(seed=1, nan_uid_frac=0.25, nan_transient=True)
    rep_s = replay_scheduler(_sched(inj, mesh=mesh), trace)
    rows.append(fault_row(rep_s, n, "inflight", "nan_transient",
                          devices=n_devices))
    rep_o = replay_scheduler(_sched(inj, overlap=True, mesh=mesh), trace)
    rows.append(fault_row(rep_o, n, "inflight_overlap", "nan_transient",
                          devices=n_devices))
    parity_ok = parity_ok and records_bitwise_equal(rep_s, rep_o)
    for r in rows:
        r["backend"] = jax.default_backend()
    return rows, parity_ok


def _start_sharded_section(budget: str):
    script = (
        "import os, json, sys\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        f"sys.path.insert(0, {REPO_ROOT!r})\n"
        "from benchmarks.bench_faults import sharded_chaos_rows\n"
        f"rows, ok = sharded_chaos_rows({budget!r})\n"
        "print('SHARDED_FAULTS=' + json.dumps([rows, ok], default=str))\n")
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
        raise RuntimeError("sharded chaos subprocess failed:\n"
                           + (stdout + stderr)[-4000:])
    line = [l for l in stdout.splitlines()
            if l.startswith("SHARDED_FAULTS=")][-1]
    rows, ok = json.loads(line[len("SHARDED_FAULTS="):])
    return rows, ok


def main(budget: str = "small", out_path: str = OUT_PATH):
    sh_proc = _start_sharded_section(budget)
    p_rows, parity_ok = parity_rows(budget)
    m_rows, overlap_parity = mix_rows(budget)
    sh_rows, sh_parity = _join_sharded_section(sh_proc)

    rows = p_rows + m_rows + sh_rows
    fault_rows = [r for r in rows if "zero_hang" in r]
    rows.append({
        "bench": "faults", "mode": "verdict",
        "zero_hang_all": all(r["zero_hang"] for r in fault_rows),
        "status_accounting_ok": all(r["status_ok"] for r in fault_rows),
        "fault_free_parity": bool(parity_ok and sh_parity),
        "overlap_parity_all": bool(overlap_parity and sh_parity),
        "mixes": sorted({r["mix"] for r in fault_rows}),
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
