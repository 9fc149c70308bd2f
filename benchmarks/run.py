"""Benchmark harness — one module per paper table/figure (+ beyond-paper).

    PYTHONPATH=src python -m benchmarks.run [--budget small|full] [--only X]
    PYTHONPATH=src python -m benchmarks.run --check

Prints one CSV-ish line per result row: ``name,us_per_call,derived``.
Figure mapping: bench_pareto (Fig 3/9), bench_wallclock (Fig 4),
bench_alpha_family (Fig 5-6), bench_cnf (Fig 1/7), bench_trajectory
(Fig 8), bench_overhead (Fig 2 + Sec 6), bench_kernels (kernel layer),
bench_cdepth_lm (beyond paper: the technique on LM serving),
bench_scheduler (in-flight continuous batching vs the drain engine).

Perf trajectory files at the repo root (uploaded as CI artifacts on every
tier-1 run): BENCH_kernels.json (bench_kernels — fused hyper_step traffic
model + timings per tableau), BENCH_serve.json (bench_serve — the
multi-rate NFE/agreement pareto), BENCH_scheduler.json
(bench_scheduler — serving-latency head-to-head, p50/p99/waste),
BENCH_wallclock.json (bench_wallclock — the real-clock overlap-vs-sync
serving race + async-dispatch mechanism + predicted-vs-measured join),
BENCH_faults.json (bench_faults — the chaos harness: zero-hang,
status accounting, and fault-free parity under seeded fault injection),
BENCH_refinery.json (bench_refinery — the closed refinement loop:
refined-vs-frozen agreement at equal NFE, capture bitwise parity, and
shadow-gate rejection cleanliness), and BENCH_flow.json (bench_flow —
the K=0 flow tier: three-tier-router vs hypersolver-only pareto,
flow-disabled bitwise parity, and escalation-path accounting).

``--check`` is the BENCH-schema smoke gate (tier-1 CI): it validates
every committed BENCH_*.json — parseable, non-empty list of rows, every
row tagged with its bench — plus per-file invariants (the scheduler
verdict row must exist; kernels rows must carry the traffic model), so a
malformed perf-trajectory file fails fast instead of at analysis time.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time
import traceback

MODULES = [
    "bench_overhead",
    "bench_pareto",
    "bench_wallclock",
    "bench_alpha_family",
    "bench_trajectory",
    "bench_cnf",
    "bench_kernels",
    "bench_cdepth_lm",
    "bench_serve",
    "bench_scheduler",
    "bench_faults",
    "bench_refinery",
    "bench_flow",
]

REPO_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))

# BENCH_*.json contract: every tracked perf-trajectory file must exist at
# the repo root, and each file's rows must contain the listed keys in at
# least one row (the row-level invariant the analysis scripts key on).
BENCH_REQUIRED = {
    "BENCH_kernels.json": ("memory_passes_fused", "hbm_bytes_fused"),
    "BENCH_serve.json": ("mean_nfe", "mode"),
    # 'devices' pins the multi-device slot-pool section (single- vs
    # sharded-pool rows, bench_scheduler.sharded_rows); 'cost_unit' pins
    # the clock tag every replay row must carry since the oracle refactor
    "BENCH_scheduler.json": ("p99_latency", "waste_steps", "devices",
                             "cost_unit"),
    # the wall-clock serving race (bench_wallclock.serving_rows):
    # 'req_per_s' pins the real-clock serving rows, 'agreement' the
    # uid-for-uid overlap-vs-sync parity every timing row must carry
    "BENCH_wallclock.json": ("req_per_s", "agreement"),
    # the chaos harness (bench_faults): 'zero_hang' pins the liveness
    # ledger every fault-mix row carries, 'mix' the fault taxonomy
    "BENCH_faults.json": ("zero_hang", "mix"),
    # the closed-loop refinery (bench_refinery): 'agreement' pins the
    # frozen-vs-refined scoring rows, 'section' the three-part layout
    "BENCH_refinery.json": ("agreement", "section"),
    # the K=0 flow tier (bench_flow): 'mean_nfe' pins the pareto rows
    # (three-tier vs hypersolver-only), 'escalated' the fault-path
    # accounting rows, 'section' the three-part layout
    "BENCH_flow.json": ("mean_nfe", "escalated", "section"),
}


def check_bench_files(root: str = REPO_ROOT) -> list:
    """Validate BENCH_*.json at the repo root; returns a list of error
    strings (empty = all good). Shared by ``--check`` and the tier-1
    test (tests/test_scheduler.py)."""
    errors = []
    found = {os.path.basename(p) for p in
             glob.glob(os.path.join(root, "BENCH_*.json"))}
    for name in BENCH_REQUIRED:
        if name not in found:
            errors.append(f"{name}: missing from repo root")
    for name in sorted(found):
        path = os.path.join(root, name)
        try:
            with open(path) as fh:
                rows = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            errors.append(f"{name}: unreadable/malformed JSON ({e})")
            continue
        if not isinstance(rows, list) or not rows:
            errors.append(f"{name}: expected a non-empty list of rows")
            continue
        bad = [i for i, r in enumerate(rows)
               if not isinstance(r, dict) or not isinstance(
                   r.get("bench"), str)]
        if bad:
            errors.append(f"{name}: rows {bad[:5]} lack a 'bench' tag")
        for key in BENCH_REQUIRED.get(name, ()):
            if not any(isinstance(r, dict) and key in r for r in rows):
                errors.append(f"{name}: no row carries required key "
                              f"{key!r}")
        if name == "BENCH_scheduler.json":
            verdicts = [r for r in rows if isinstance(r, dict)
                        and r.get("mode") == "verdict"]
            if not verdicts:
                errors.append(f"{name}: missing the verdict row "
                              "(inflight_wins_p99 scoreboard)")
            else:
                for key in ("inflight_wins_p99", "sharded_pool_ok"):
                    if key not in verdicts[0]:
                        errors.append(f"{name}: verdict row lacks "
                                      f"{key!r}")
            if not any(isinstance(r, dict) and r.get("devices", 0) > 1
                       for r in rows):
                errors.append(f"{name}: no multi-device slot-pool row "
                              "(devices > 1) — bench_scheduler's sharded "
                              "section is missing")
            errors.extend(_check_oracle_section(name, rows, root))
        if name == "BENCH_wallclock.json":
            errors.extend(_check_wallclock_section(name, rows))
        if name == "BENCH_faults.json":
            errors.extend(_check_faults_section(name, rows))
        if name == "BENCH_refinery.json":
            errors.extend(_check_refinery_section(name, rows))
        if name == "BENCH_flow.json":
            errors.extend(_check_flow_section(name, rows))
    return errors


def _check_flow_section(name: str, rows: list) -> list:
    """K=0 flow-tier invariants: pareto rows for BOTH router variants
    with the three-tier side at equal-or-better agreement and STRICTLY
    lower mean NFE (and actually serving flow traffic — a zero-traffic
    'win' is vacuous), flow-disabled parity rows for all three serving
    loops each bitwise at parity, escalation rows whose poisoned flow
    evals were requeued into the K-ladder with accounting that closes,
    and the verdict scoreboard all True."""
    errors = []
    par = {r.get("variant"): r for r in rows if isinstance(r, dict)
           and r.get("section") == "pareto"}
    for variant in ("hyper_multirate", "three_tier"):
        if variant not in par:
            errors.append(f"{name}: no pareto row for the {variant!r} "
                          "variant — the head-to-head needs both sides")
    if len(par) == 2:
        h, f = par["hyper_multirate"], par["three_tier"]
        if not (f.get("agreement", 0) >= h.get("agreement", 1)):
            errors.append(f"{name}: three-tier agreement "
                          f"{f.get('agreement')} fell below the "
                          f"hypersolver-only {h.get('agreement')}")
        if not (f.get("mean_nfe", 1e9) < h.get("mean_nfe", 0)):
            errors.append(f"{name}: three-tier mean NFE "
                          f"{f.get('mean_nfe')} is not strictly below "
                          f"the hypersolver-only {h.get('mean_nfe')}")
        if not f.get("flow_served", 0) > 0:
            errors.append(f"{name}: the three-tier pareto row served "
                          "zero flow-tier requests — the comparison is "
                          "vacuous")
    dis = {r.get("mode"): r for r in rows if isinstance(r, dict)
           and r.get("section") == "flow_disabled_parity"}
    for loop in ("engine", "inflight", "inflight_overlap"):
        if loop not in dis:
            errors.append(f"{name}: no flow-disabled parity row for "
                          f"the {loop!r} loop")
        elif dis[loop].get("parity") is not True:
            errors.append(f"{name}: flow-disabled parity row for "
                          f"{loop!r} is not at parity — attaching a "
                          "disabled flow head perturbed the ladder")
    esc = [r for r in rows if isinstance(r, dict)
           and r.get("section") == "escalation" and "escalated" in r]
    if not esc:
        errors.append(f"{name}: no escalation rows (flow fault path)")
    elif not any(r.get("escalated", 0) > 0 for r in esc):
        errors.append(f"{name}: no escalation row recorded a poisoned "
                      "flow eval requeued into the K-ladder")
    verdicts = [r for r in rows if isinstance(r, dict)
                and r.get("mode") == "verdict"]
    if not verdicts:
        errors.append(f"{name}: missing the verdict row "
                      "(three_tier_dominates scoreboard)")
    else:
        for key in ("three_tier_dominates", "flow_disabled_parity",
                    "escalation_accounted", "zero_hang"):
            if verdicts[0].get(key) is not True:
                errors.append(f"{name}: verdict {key} is not True — "
                              "the flow-tier contract regressed")
    return errors


def _check_refinery_section(name: str, rows: list) -> list:
    """Closed-loop-refinery invariants: frozen AND refined scoring rows
    (the head-to-head needs both), capture-parity rows for all three
    serving loops each at parity, a shadow-gate row whose corrupted
    candidate was rejected, and the verdict scoreboard — the refined g
    must beat the frozen g on agreement at EQUAL mean NFE, capture must
    be bitwise free, and a rejected candidate must never be observable
    in serving outputs."""
    errors = []
    scoring = [r for r in rows if isinstance(r, dict)
               and r.get("section") == "refinement"
               and r.get("variant") in ("frozen", "refined")]
    for variant in ("frozen", "refined"):
        if not any(r.get("variant") == variant for r in scoring):
            errors.append(f"{name}: no refinement scoring row for the "
                          f"{variant!r} variant — the head-to-head "
                          "needs both sides")
    cap = {r.get("mode"): r for r in rows if isinstance(r, dict)
           and r.get("section") == "capture_parity"}
    for loop in ("inflight", "inflight_overlap", "engine"):
        if loop not in cap:
            errors.append(f"{name}: no capture-parity row for the "
                          f"{loop!r} loop")
        elif cap[loop].get("parity") is not True:
            errors.append(f"{name}: capture-parity row for {loop!r} is "
                          "not at parity — capture perturbed serving "
                          "completions")
    gates = [r for r in rows if isinstance(r, dict)
             and r.get("section") == "shadow_gate"]
    if not gates:
        errors.append(f"{name}: missing the shadow-gate rejection row")
    elif not (gates[0].get("candidate_rejected")
              and gates[0].get("parity")):
        errors.append(f"{name}: shadow-gate row shows the corrupted "
                      "candidate leaked into serving (rejected="
                      f"{gates[0].get('candidate_rejected')}, parity="
                      f"{gates[0].get('parity')})")
    verdicts = [r for r in rows if isinstance(r, dict)
                and r.get("mode") == "verdict"]
    if not verdicts:
        errors.append(f"{name}: missing the verdict row "
                      "(refined_beats_frozen scoreboard)")
    else:
        for key in ("refined_beats_frozen", "equal_nfe",
                    "capture_parity", "shadow_gate_clean"):
            if verdicts[0].get(key) is not True:
                errors.append(f"{name}: verdict {key} is not True — "
                              "the closed-loop contract regressed")
    return errors


def _check_faults_section(name: str, rows: list) -> list:
    """Chaos-bench invariants: every fault-mix row terminal-accounted
    and hang-free, a fault-free-parity check that PASSED (the hardened
    loops are bitwise the old loops when nothing is injected), sync ==
    overlap under identical fault schedules, and a multi-device chaos
    row (the quarantine works on the sharded pool too)."""
    errors = []
    fault_rows = [r for r in rows if isinstance(r, dict)
                  and "zero_hang" in r]
    if not fault_rows:
        errors.append(f"{name}: no fault-mix rows (zero_hang ledger)")
    hung = [f"{r.get('mode')}/{r.get('mix')}" for r in fault_rows
            if not r.get("zero_hang")]
    if hung:
        errors.append(f"{name}: rows {hung} lost requests — a submitted "
                      "uid never reached a terminal record")
    bad_acct = [f"{r.get('mode')}/{r.get('mix')}" for r in fault_rows
                if not r.get("status_ok")]
    if bad_acct:
        errors.append(f"{name}: rows {bad_acct} have a status histogram "
                      "that does not sum to the submitted count")
    if not any(isinstance(r, dict) and r.get("devices", 0) > 1
               for r in rows):
        errors.append(f"{name}: no multi-device chaos row (devices > 1) "
                      "— bench_faults' sharded section is missing")
    verdicts = [r for r in rows if isinstance(r, dict)
                and r.get("mode") == "verdict"]
    if not verdicts:
        errors.append(f"{name}: missing the verdict row (zero_hang_all "
                      "scoreboard)")
    else:
        for key in ("zero_hang_all", "fault_free_parity",
                    "status_accounting_ok", "overlap_parity_all"):
            if verdicts[0].get(key) is not True:
                errors.append(f"{name}: verdict {key} is not True — "
                              "the hardening contract regressed")
    return errors


def _check_wallclock_section(name: str, rows: list) -> list:
    """Wall-clock-bench invariants: a sync AND an overlap serving row
    (the race needs both lanes), every serving row at agreement 1.0
    (a timing row for loops that diverged is meaningless — the overlap
    loop must be observationally the sync loop before its clock
    counts), a predicted-vs-measured row carrying BOTH unit tags (the
    device_us/wall_us join must stay ratio-able, never summable), and
    the verdict scoreboard with its async-dispatch mechanism check."""
    errors = []
    serving = [r for r in rows if isinstance(r, dict)
               and r.get("section") == "serving"]
    for loop in ("sync", "overlap"):
        if not any(r.get("loop") == loop for r in serving):
            errors.append(f"{name}: no serving row for the {loop!r} "
                          "loop — the wall-clock race needs both lanes")
    bad = [r.get("trace") for r in serving if r.get("agreement") != 1.0]
    if bad:
        errors.append(f"{name}: serving rows with agreement != 1.0 on "
                      f"traces {bad} — overlap diverged from sync, the "
                      "timings are void")
    pvm = [r for r in rows if isinstance(r, dict)
           and r.get("section") == "predicted_vs_measured"]
    if not pvm:
        errors.append(f"{name}: missing the predicted-vs-measured "
                      "section (roofline device_us vs measured wall_us)")
    elif not all(r.get("predicted_unit") == "device_us"
                 and r.get("measured_unit") == "wall_us" for r in pvm):
        errors.append(f"{name}: predicted-vs-measured rows must tag "
                      "predicted_unit='device_us' and "
                      "measured_unit='wall_us'")
    verdicts = [r for r in rows if isinstance(r, dict)
                and r.get("mode") == "verdict"]
    if not verdicts:
        errors.append(f"{name}: missing the verdict row "
                      "(overlap_wins_wallclock scoreboard)")
    else:
        for key in ("overlap_wins_wallclock", "agreement_all",
                    "async_dispatch_ok", "host_cpus"):
            if key not in verdicts[0]:
                errors.append(f"{name}: verdict row lacks {key!r}")
        if verdicts[0].get("agreement_all") != 1.0:
            errors.append(f"{name}: verdict agreement_all != 1.0 — "
                          "the overlap loop diverged from the sync "
                          "oracle on some trace")
    return errors


def _check_oracle_section(name: str, rows: list, root: str) -> list:
    """Scheduler-bench oracle-section invariants: a roofline-clock row
    must exist (cost_unit='device_us'), tuner verdict rows must exist,
    and each tuner row's chosen knobs must match the persisted config in
    artifacts/tuned/<cell>.json — a stale tuned config (someone re-ran
    the autotuner without regenerating the bench, or vice versa) fails
    here instead of silently shipping two disagreeing verdicts."""
    errors = []
    if not any(isinstance(r, dict) and r.get("cost_unit") == "device_us"
               for r in rows):
        errors.append(f"{name}: no roofline-oracle row "
                      "(cost_unit='device_us') — bench_scheduler's "
                      "oracle section is missing")
    tuner_rows = [r for r in rows if isinstance(r, dict)
                  and r.get("mode") == "tuner"]
    if not tuner_rows:
        errors.append(f"{name}: no tuner verdict rows (mode='tuner') — "
                      "run python -m repro.launch.autotune or regenerate "
                      "the bench")
    for r in tuner_rows:
        cell = r.get("cell", "?")
        path = os.path.join(root, "artifacts", "tuned", f"{cell}.json")
        if not os.path.exists(path):
            errors.append(f"{name}: tuner row {cell!r} has no persisted "
                          f"config at artifacts/tuned/{cell}.json")
            continue
        try:
            with open(path) as fh:
                tuned = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            errors.append(f"artifacts/tuned/{cell}.json: unreadable/"
                          f"malformed JSON ({e})")
            continue
        if tuned.get("chosen") != r.get("chosen"):
            errors.append(
                f"artifacts/tuned/{cell}.json is stale relative to the "
                f"tuner verdict in {name}: chosen {tuned.get('chosen')} "
                f"vs {r.get('chosen')} — re-run the autotune sweep and "
                "regenerate the bench together")
    return errors


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", default="small", choices=["small", "full"])
    ap.add_argument("--only", default=None)
    ap.add_argument("--check", action="store_true",
                    help="validate committed BENCH_*.json schemas and exit "
                         "(the tier-1 fail-fast gate; runs no benchmarks)")
    args = ap.parse_args()

    if args.check:
        errors = check_bench_files()
        for e in errors:
            print(f"# BENCH-CHECK FAIL: {e}")
        if errors:
            raise SystemExit(1)
        print(f"# BENCH-CHECK OK: {sorted(BENCH_REQUIRED)}")
        return

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    out_dir = os.path.join(os.path.dirname(__file__), "..", "artifacts")
    os.makedirs(out_dir, exist_ok=True)
    all_rows = []
    failures = []
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            rows = mod.main(budget=args.budget)
            dt = time.time() - t0
            for r in rows:
                derived = {k: v for k, v in r.items() if k != "bench"}
                print(f"{r['bench']},{dt / max(len(rows), 1) * 1e6:.0f},"
                      f"{json.dumps(derived, default=str)}")
            all_rows.extend(rows)
            print(f"# {mod_name}: {len(rows)} rows in {dt:.1f}s")
        except Exception as e:
            traceback.print_exc()
            failures.append((mod_name, str(e)))
    with open(os.path.join(out_dir, "bench_results.json"), "w") as f:
        json.dump(all_rows, f, indent=1, default=str)
    if failures:
        print(f"# FAILURES: {failures}")
        raise SystemExit(1)
    print(f"# total rows: {len(all_rows)} -> artifacts/bench_results.json")


if __name__ == "__main__":
    main()
