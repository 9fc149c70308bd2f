"""Multi-rate serving pareto: error-controlled per-request step sizes
(launch/engine.py) vs fixed-K serving, on the NFE-vs-agreement axis.

    PYTHONPATH=src python benchmarks/bench_serve.py --solver hyper_euler

Workloads:
  * node — the paper's MNIST-family Neural ODE (trained + HyperEuler
    fitted once, cached in artifacts/ by benchmarks/common.py) serving a
    heterogeneous request mix: nominal synthetic images plus a stiff slice
    (higher contrast -> genuinely harder dynamics). Reference = dopri5 at
    tight tolerances, the paper's ground-truth semantics.
  * lm — the continuous-depth LM (models/cdepth.py): same engine, same
    accounting, reference = dopri5 solve of the depth ODE.

Quality metrics per request, against the reference prediction:
  * argmax_agreement — predicted class/token match;
  * soft_agreement   — softmax overlap sum_c min(p_c, p_ref_c)
    (= 1 - total variation; smooth in integration error, so the pareto is
    visible even where argmax saturates).

The fixed-K baseline runs through the SAME engine (FixedController), so
the comparison isolates the policy, not the plumbing. The JSON written to
BENCH_serve.json includes a ``verdict`` row: multirate_wins is True when
some multi-rate point matches a fixed point's agreement at strictly fewer
mean NFEs (or beats it at equal NFEs) — the tracked pareto scoreboard.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from functools import partial

if __name__ == "__main__":  # runnable as a script from anywhere
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import CACHE, fit_image_hypersolver, train_image_node
from repro.core import FixedGrid, odeint_dopri5
from repro.data import synthetic_images
from repro.launch.engine import (
    EngineConfig, MultiRateEngine, lm_depth_model, node_depth_model,
)
from repro.models.conv_node import mnist_g_apply

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")


# ---------------------------------------------------------------- metrics ----

def _soft_agree(logits: np.ndarray, ref_p: np.ndarray) -> float:
    """Softmax overlap with the reference distribution, in [0, 1]."""
    p = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    return float(np.minimum(p, ref_p).sum(-1).mean())


def _evaluate(results, ref_p, ref_top):
    soft, top, nfes, Ks = [], [], [], []
    for i, r in enumerate(results):
        soft.append(_soft_agree(r.outputs, ref_p[i]))
        p_top = np.asarray(r.outputs).argmax(-1)
        top.append(float(np.mean(p_top == ref_top[i])))
        nfes.append(r.nfe)
        Ks.append(r.K)
    return {
        "mean_nfe": round(float(np.mean(nfes)), 3),
        "argmax_agreement": round(float(np.mean(top)), 4),
        "soft_agreement": round(float(np.mean(soft)), 4),
        "k_histogram": {int(k): int(c) for k, c in
                        zip(*np.unique(Ks, return_counts=True))},
    }


def pareto_verdict(fixed_rows, mr_rows):
    """Does some multi-rate point weakly dominate a fixed-K point?

    Win = same-or-better agreement at strictly fewer mean NFEs, or better
    agreement at the same NFEs, on either agreement metric."""
    wins = []
    for metric in ("argmax_agreement", "soft_agreement"):
        for m in mr_rows:
            for fx in fixed_rows:
                same_quality = m[metric] >= fx[metric] - 1e-9
                cheaper = m["mean_nfe"] < fx["mean_nfe"] - 1e-9
                better = (m[metric] > fx[metric] + 1e-9
                          and m["mean_nfe"] <= fx["mean_nfe"] + 1e-9)
                if (same_quality and cheaper) or better:
                    wins.append({
                        "metric": metric,
                        "multirate": {"tol": m["tol"],
                                      "mean_nfe": m["mean_nfe"],
                                      metric: m[metric]},
                        "fixed": {"K": fx["K"], "mean_nfe": fx["mean_nfe"],
                                  metric: fx[metric]},
                    })
    return {"multirate_wins": bool(wins), "witnesses": wins[:4]}


def _sweep(model, ref_p, ref_top, xs, buckets, tol_grid, max_batch,
           workload, solver):
    """Fixed-K and multi-rate tolerance sweeps through the same engine."""
    fixed_rows = []
    for K in buckets:
        eng = MultiRateEngine(model, EngineConfig(
            buckets=(K,), controller="fixed", fixed_K=K,
            max_batch=max_batch, solver=solver))
        row = _evaluate(eng.run(xs), ref_p, ref_top)
        row.update(bench="serve", workload=workload, solver=solver,
                   mode="fixed", K=K)
        fixed_rows.append(row)

    mr_rows = []
    for tol in tol_grid:
        eng = MultiRateEngine(model, EngineConfig(
            buckets=buckets, tol=float(tol), max_batch=max_batch,
            solver=solver))
        row = _evaluate(eng.run(xs), ref_p, ref_top)
        row.update(bench="serve", workload=workload, solver=solver,
                   mode="multirate", tol=round(float(tol), 4),
                   probe_nfe=eng.probe_nfe,
                   controller=type(eng.controller).__name__)
        mr_rows.append(row)

    verdict = pareto_verdict(fixed_rows, mr_rows)
    verdict.update(bench="serve", workload=workload, solver=solver,
                   mode="verdict")
    return fixed_rows + mr_rows + [verdict]


def _tol_grid(model, xs, buckets, max_batch):
    """Anchor the tolerance sweep on the measured probe-error scale, so the
    sweep lands on the interesting part of the pareto for any workload.
    Probe-only — no bucket solves are spent on calibration."""
    eng = MultiRateEngine(model, EngineConfig(buckets=buckets, tol=1.0,
                                              max_batch=max_batch))
    _, errs = eng.probe(xs)
    med = float(np.median(errs))
    return [med * f for f in (1.3, 1.1, 0.9, 0.7, 0.5, 0.35, 0.2)]


# -------------------------------------------------------------- workloads ----

def node_workload(budget: str, solver: str):
    """Heterogeneous image-classification traffic on the paper's MNIST-
    family Neural ODE: nominal requests plus a stiff (2.5x contrast)
    slice."""
    node, params = train_image_node()
    gp = None
    if solver.startswith("hyper_"):
        gp = fit_image_hypersolver(node, params,
                                   base=solver[len("hyper_"):], K=10)
    n_nom, n_stiff = (96, 32) if budget != "tiny" else (24, 8)
    xa, _ = synthetic_images("mnist28", n_nom, seed=42)
    xb, _ = synthetic_images("mnist28", n_stiff, seed=43)
    xs = np.concatenate([np.asarray(xa), 2.5 * np.asarray(xb)], axis=0)

    z0 = node.hx_apply(params, jnp.asarray(xs))
    f = node.field(params, jnp.asarray(xs))
    ref_traj, ref_nfe = odeint_dopri5(f, z0, FixedGrid.over(0.0, 1.0, 1),
                                      atol=1e-6, rtol=1e-6)
    ref_logits = node.hy_apply(params, ref_traj[-1])
    ref_p = np.asarray(jax.nn.softmax(ref_logits, -1))

    model = node_depth_model(node, params, solver=solver,
                             g_apply=mnist_g_apply if gp is not None else None,
                             g_params=gp)
    buckets = (1, 2, 3, 4, 6, 8)
    tols = _tol_grid(model, xs, buckets, 32)
    rows = _sweep(model, ref_p, ref_p.argmax(-1), xs, buckets, tols, 32,
                  "node", solver)
    for r in rows:
        r["reference_nfe"] = int(ref_nfe)
    return rows


def lm_workload(budget: str, solver: str):
    """The continuous-depth LM through the same engine: mixed prompt
    difficulty, reference = dopri5 solve of the depth ODE."""
    from benchmarks.bench_cdepth_lm import train_small_lm
    from repro.checkpoint import CheckpointManager
    from repro.data import token_batches
    from repro.models.cdepth import (
        apply_tail, cdepth_residual_loss, depth_field, lm_g_init,
    )
    from repro.models.lm import _embed, group_layout
    from repro.optim import adamw, apply_updates, clip_by_global_norm

    cfg, params = train_small_lm(150 if budget != "full" else 600)
    _, n_groups, _ = group_layout(cfg)

    g_params = None
    if solver.startswith("hyper_"):
        # one correction shared across serving buckets: mixed-K residual fit
        cm = CheckpointManager(os.path.join(CACHE, "lm_g_serve"), keep=1)
        g_params = lm_g_init(jax.random.PRNGKey(2), cfg, rank=32,
                             param_dtype=jnp.float32)
        iters = 150
        latest = cm.latest_step()
        if latest is not None and latest >= iters:
            g_params = cm.restore(latest, jax.eval_shape(lambda: g_params))
        else:
            opt = adamw(3e-3)
            st = opt.init(g_params)

            @jax.jit
            def fit(gp, st, i, batch):
                def loss(gg):
                    return sum(cdepth_residual_loss(params, gg, cfg, batch, K)
                               for K in (2, 4, 8)) / 3.0
                l, g = jax.value_and_grad(loss)(gp)
                g, _ = clip_by_global_norm(g, 1.0)
                u, st = opt.update(g, st, gp, i)
                return apply_updates(gp, u), st, l

            it = token_batches(cfg.vocab, 4, 32, seed=13)
            batch, _ = next(it)
            for i in range(iters):
                if i % 10 == 0:
                    batch, _ = next(it)
                g_params, st, _ = fit(g_params, st, i, batch)
            cm.save(iters, g_params)

    B, S = (16, 24) if budget != "tiny" else (6, 16)
    rng = np.random.RandomState(0)
    easy = np.repeat(rng.randint(0, cfg.vocab, (B // 2, 1)), S, axis=1)
    hard = rng.randint(0, cfg.vocab, (B - B // 2, S))
    toks = np.concatenate([easy, hard], axis=0).astype(np.int32)

    h0 = _embed(params, cfg, jnp.asarray(toks))
    f = partial(depth_field(cfg), params)
    ref_traj, ref_nfe = odeint_dopri5(f, h0, FixedGrid.over(0.0, 1.0, 1),
                                      atol=1e-3, rtol=1e-3)
    ref_logits = apply_tail(params, cfg, ref_traj[-1])
    ref_p = np.asarray(jax.nn.softmax(ref_logits, -1))

    model = lm_depth_model(params, cfg, solver=solver, g_params=g_params)
    buckets = (2, 4, 8, 16)
    tols = _tol_grid(model, toks, buckets, 16)
    rows = _sweep(model, ref_p, ref_p.argmax(-1), toks, buckets, tols, 16,
                  "lm", solver)
    for r in rows:
        r["reference_nfe"] = int(ref_nfe)
        r["full_depth_groups"] = n_groups
    return rows


# ------------------------------------------------------------------- main ----

def main(budget: str = "small", solver: str = "hyper_euler",
         workload: str = "both", out_path: str = OUT_PATH):
    rows = []
    if workload in ("node", "both"):
        rows += node_workload(budget, solver)
    if workload in ("lm", "both"):
        rows += lm_workload(budget, solver)
    with open(out_path, "w") as fh:
        json.dump(rows, fh, indent=1, default=str)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", default="small",
                    choices=["tiny", "small", "full"])
    ap.add_argument("--solver", default="hyper_euler")
    ap.add_argument("--workload", default="both",
                    choices=["node", "lm", "both"])
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    for r in main(args.budget, args.solver, args.workload, args.out):
        print(r)
