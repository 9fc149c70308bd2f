"""K=0 flow-tier bench (PR 10: the Neural-Flows fast tier).

    PYTHONPATH=src python benchmarks/bench_flow.py [--budget small]

Trains BOTH learned heads in-bench off one residual ledger — the
hypersolver correction g (ledger_fitting_loss) and the K=0 flow head F
(flow_fitting_loss; for the structured F = z + eps*dz + eps^{p+1}*net
the two losses are the SAME fitting problem) — then serves a held-out
heterogeneous mix and writes BENCH_flow.json with three sections:

  * **pareto** — the headline: the three-tier router (flow / hyper /
    high-K, ``EngineConfig.flow_threshold`` at the TierRouter default)
    must reach EQUAL-OR-BETTER argmax agreement against a fine frozen
    reference at STRICTLY LOWER mean NFE than hypersolver-only
    multi-rate on the same mix — probe-easy requests collapse to one
    net eval instead of the smallest bucket's solve.
  * **flow_disabled_parity** — ACCEPTANCE: with the flow tier disabled
    (flow_threshold=0) a flow-capable model's completions are
    uid-for-uid bitwise identical to a model with no flow head at all —
    engine, in-flight sync, and in-flight overlap (the tier is pure
    packing policy; attaching it must not perturb the ladder).
  * **escalation** — ACCEPTANCE: under seeded flow-eval NaN poisoning
    (``FaultInjector.flow_nan_frac``) every poisoned request escalates
    into the K-bucket ladder and completes with real outputs
    (status='escalated', the flow attempt's nfe billed), the status
    histogram sums to the submitted count, no request hangs, and the
    sync and overlap loops agree bitwise under the identical fault
    schedule.

The verdict row is the tracked scoreboard: ``three_tier_dominates``,
``flow_disabled_parity``, ``escalation_accounted``, ``zero_hang``.
``benchmarks/run.py --check`` enforces all four.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import json
import sys

REPO_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
if __name__ == "__main__":  # runnable as a script from anywhere
    sys.path.insert(0, REPO_ROOT)

import numpy as np

from benchmarks.bench_faults import records_bitwise_equal
from repro.distributed.fault import FaultInjector
from repro.launch.engine import EngineConfig, MultiRateEngine, STATUSES
from repro.launch.refinery import Refinery, RefineryConfig, ResidualLedger
from repro.launch.scheduler import InflightScheduler
from repro.launch.workload import (
    heterogeneous_requests, poisson_trace, replay_engine, replay_scheduler,
    status_counts, toy_flow_classifier, toy_refinable_classifier,
)

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_flow.json")

D_FEAT = 32
BUCKETS = (2, 4, 8, 16)
# Probe errors here are L2 norms over the d=32 embedding state, and the
# toy mix is bimodal: trained-g estimates settle near ~0.07 for the easy
# class and ~7 for the stiff class. tol=0.35 puts the router's flow
# gate (0.25 * tol ~ 0.0875) comfortably above the easy mode and far
# below the hard one, so the three tiers all see traffic — the same
# calibration serve.py owners do against their own error scale
# (docs/serving.md).
TOL = 0.35
REF_K = 64
HIDDEN = 32


def _budget(budget: str):
    return {
        "tiny": dict(n_train=128, n_eval=96, g_steps=1500, f_iters=1500),
        "small": dict(n_train=256, n_eval=192, g_steps=4000, f_iters=4000),
        "full": dict(n_train=512, n_eval=384, g_steps=6000, f_iters=6000),
    }.get(budget, None) or _budget("small")


def _ecfg(flow_threshold: float = 0.0):
    return EngineConfig(buckets=BUCKETS, tol=TOL, max_batch=16,
                        solver="hyper_euler", fused=True,
                        flow_threshold=flow_threshold)


def _reference(model, xs: np.ndarray) -> np.ndarray:
    """Fine frozen reference: the BASE tableau at REF_K steps — the same
    ground-truth proxy the refinery's shadow scorer uses."""
    import jax
    import jax.numpy as jnp

    from repro.core import Integrator

    ref = Integrator(tableau=model.integ.tableau)

    @jax.jit
    def run(p, x):
        z0 = model.embed(p, x)
        Ks = jnp.full((x.shape[0],), REF_K, jnp.int32)
        zT = ref.solve_multirate(model.field_of(p, x), z0, model.span, Ks,
                                 REF_K)
        return model.readout(p, x, zT)

    return np.asarray(run(model.params, jnp.asarray(xs)))


def _trained_model(budget: str):
    """One ledger, two heads: capture residual rows from a training mix
    at the serving step sizes (bucket eps AND the flow tier's full
    span), fit g on them with the refinery trainer, fit F on the SAME
    ledger with core.train.train_flowhead, and return the model carrying
    both trained heads."""
    import jax.numpy as jnp

    from repro.core.train import FlowTrainConfig, train_flowhead

    b = _budget(budget)
    model = toy_flow_classifier(d=D_FEAT, hidden=HIDDEN)
    ledger = ResidualLedger(model, capacity=4096, capture_rate=1.0,
                            seed=0, holdout_every=0)

    xs = heterogeneous_requests(b["n_train"], D_FEAT, seed=1)
    h = model.span[1] - model.span[0]
    z0 = model.embed(model.params, jnp.asarray(xs))
    dz0 = model.field_of(model.params, jnp.asarray(xs))(model.span[0], z0)
    z_mid = z0 + (h / 2) * dz0          # a half-span Euler interior state
    n = len(xs)
    for K in (1,) + BUCKETS:            # K=1 is the flow tier's eps = h
        eps = np.full(n, h / K, np.float32)
        ledger.capture(xs, z0, np.zeros(n, np.float32), eps)
        ledger.capture(xs, z_mid, np.full(n, h / 2, np.float32), eps)

    # g: the refinery's trainer over ledger_fitting_loss
    refin = Refinery(model, ledger,
                     RefineryConfig(steps_per_tick=b["g_steps"],
                                    batch_size=128, min_fill=64, lr=5e-3,
                                    total_steps=b["g_steps"],
                                    ckpt_every=10 ** 9, seed=0),
                     param_site="g")
    refin.train_tick()

    # F: same ledger rows through core/train.py::train_flowhead
    fp, losses = train_flowhead(
        model.flow_apply, model.flow_params, ledger,
        FlowTrainConfig(iters=b["f_iters"], batch_size=128, lr=5e-3,
                        order=model.integ.order, seed=0))
    return dataclasses.replace(model, g_params=refin.candidate,
                               flow_params=fp), float(losses[-1])


# --------------------------------------------------------------- pareto ----

def pareto_rows(budget: str = "small"):
    """Three-tier router vs hypersolver-only multi-rate on a held-out
    heterogeneous mix: equal-or-better agreement at strictly lower mean
    NFE, or the tier is not paying for its routing."""
    b = _budget(budget)
    model, final_loss = _trained_model(budget)
    xs = heterogeneous_requests(b["n_eval"], D_FEAT, seed=7)
    ref_top = np.argmax(_reference(model, xs), -1)

    rows, stats = [], {}
    for variant, ft in (("hyper_multirate", 0.0), ("three_tier", None)):
        if ft is None:
            from repro.core.controllers import TierRouter
            ft = TierRouter().flow_threshold   # the live router default
        eng = MultiRateEngine(model, _ecfg(ft))
        recs = sorted(eng.run(xs), key=lambda c: c.uid)
        outs = np.stack([c.outputs for c in recs])
        agree = float((np.argmax(outs, -1) == ref_top).mean())
        mean_nfe = float(np.mean([c.nfe for c in recs]))
        flow_served = sum(1 for c in recs if c.K == 0)
        stats[variant] = (agree, mean_nfe)
        rows.append({"bench": "flow", "section": "pareto",
                     "variant": variant, "flow_threshold": ft,
                     "agreement": agree, "mean_nfe": mean_nfe,
                     "flow_served": flow_served,
                     "requests": len(xs), "buckets": list(BUCKETS),
                     "tol": TOL, "ref_K": REF_K,
                     "flow_final_loss": final_loss})
    (ag_h, nfe_h), (ag_f, nfe_f) = stats["hyper_multirate"], \
        stats["three_tier"]
    dominates = bool(ag_f >= ag_h and nfe_f < nfe_h)
    served_flow = any(r["variant"] == "three_tier" and r["flow_served"] > 0
                      for r in rows)
    return rows, dominates and served_flow, model


# ------------------------------------------------- flow-disabled parity ----

def parity_rows(budget: str = "small"):
    """flow_threshold=0 on a flow-capable model must be bitwise the
    flowless model's serve — all three loops, uid for uid."""
    n = {"tiny": 24, "small": 48, "full": 96}.get(budget, 48)
    xs = heterogeneous_requests(n, D_FEAT, seed=17)
    trace = poisson_trace(xs, rate=0.5, seed=211)
    ecfg = _ecfg(0.0)

    def loops(make_model):
        eng = replay_engine(MultiRateEngine(make_model(), ecfg), trace)
        sy = replay_scheduler(InflightScheduler(make_model(), ecfg,
                                                slots=8, seg=2), trace)
        ov = replay_scheduler(InflightScheduler(make_model(), ecfg,
                                                slots=8, seg=2,
                                                overlap=True), trace)
        return {"engine": eng, "inflight": sy, "inflight_overlap": ov}

    with_flow = loops(lambda: toy_flow_classifier(d=D_FEAT))
    without = loops(lambda: toy_refinable_classifier(d=D_FEAT))
    rows, ok = [], True
    for loop in ("engine", "inflight", "inflight_overlap"):
        parity = records_bitwise_equal(with_flow[loop], without[loop])
        ok &= parity
        rows.append({"bench": "flow", "section": "flow_disabled_parity",
                     "mode": loop, "submitted": n,
                     "parity": bool(parity)})
    return rows, bool(ok)


# ------------------------------------------------------------ escalation ----

def escalation_rows(budget: str, model):
    """Seeded flow-eval NaN chaos: poisoned flow rows must escalate into
    the ladder and complete with real outputs; accounting must close;
    sync and overlap must agree bitwise under the identical schedule."""
    n = {"tiny": 32, "small": 64, "full": 128}.get(budget, 64)
    xs = heterogeneous_requests(n, D_FEAT, seed=23)
    trace = poisson_trace(xs, rate=0.5, seed=311)
    ecfg = _ecfg(0.25)

    def injector():
        return FaultInjector(seed=5, flow_nan_frac=0.7)

    reports = {}
    reports["engine"] = replay_engine(
        MultiRateEngine(model, ecfg, fault_injector=injector()), trace)
    scheds = {}
    for loop, ov in (("inflight", False), ("inflight_overlap", True)):
        s = InflightScheduler(model, ecfg, slots=8, seg=2, overlap=ov,
                              fault_injector=injector())
        reports[loop] = replay_scheduler(s, trace)
        scheds[loop] = s

    rows, esc_total, accounted, zero_hang = [], 0, True, True
    for loop, rep in reports.items():
        sc = status_counts(rep)
        esc = sc["escalated"]
        esc_total += esc
        closes = sum(sc.values()) == n and len(rep.records) == n
        real = all(r.outputs is not None and np.isfinite(r.outputs).all()
                   for r in rep.records if r.status == "escalated")
        accounted &= closes and real
        zero_hang &= closes
        rows.append({"bench": "flow", "section": "escalation",
                     "mode": loop, "mix": "flow_nan", "submitted": n,
                     "status": sc, "escalated": esc,
                     "zero_hang": bool(closes),
                     "escalated_outputs_real": bool(real)})
    overlap_parity = records_bitwise_equal(reports["inflight"],
                                           reports["inflight_overlap"])
    rows.append({"bench": "flow", "section": "escalation",
                 "mode": "sync_vs_overlap", "mix": "flow_nan",
                 "parity": bool(overlap_parity),
                 "flow_served": scheds["inflight"].total_flow_served,
                 "escalated": scheds["inflight"].total_escalated})
    ok = bool(esc_total > 0 and accounted and overlap_parity)
    return rows, ok, bool(zero_hang)


def main(budget: str = "small", out_path: str = OUT_PATH):
    par_rows, dominates, model = pareto_rows(budget)
    dis_rows, parity_ok = parity_rows(budget)
    esc_rows, esc_ok, zero_hang = escalation_rows(budget, model)
    rows = par_rows + dis_rows + esc_rows
    rows.append({
        "bench": "flow", "mode": "verdict",
        "three_tier_dominates": bool(dominates),
        "flow_disabled_parity": bool(parity_ok),
        "escalation_accounted": bool(esc_ok),
        "zero_hang": bool(zero_hang),
        "statuses": list(STATUSES),
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
