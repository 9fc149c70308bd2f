"""Smoke run of continuous-depth LM serving on a TPU.

Builds ``qwen3_4b`` at its published widths (36 layers, d_model 2560,
vocab 151936, bf16) from ``--seed`` and drives the normal serving path,
``init_lm -> lm_depth_model -> InflightScheduler -> replay_scheduler``,
with the fused ``hyper_step`` kernel. On one chip:

  1. reference: the discrete full-depth ``lm_forward``;
  2. in-flight euler at fixed K = n_groups: argmax agreement with (1)
     must be at least 0.95 (Euler at K = n_groups is the discrete stack
     up to bf16 recombination rounding);
  3. in-flight multi-rate hyper_euler with a zero-init parametric g,
     through the sync and the overlap tick: completions identical bit for
     bit, every status ``ok``, every output finite;
  4. the ``hyper_step`` kernel against its jnp oracle at the served width.

The compiled segment cells of (2) and (3) must hold the kernel
(``tpu_custom_call``). ``--chips 4`` runs only the sharded slot pool
(``make_serving_mesh(4)``, 8 slots) against the one-device pool on the
same requests.

Each phase prints its compile and wall seconds, what it checked, the
device kind and the device's peak memory. The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``, printed
only when every phase passed on a TPU; a failed phase raises, and
without a TPU nothing runs: the exit code is then non-zero and no
result is printed.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # sharded pool on four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --reduced     # CPU rehearsal
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if not os.path.isdir(os.path.join(SRC, "repro")):
    sys.exit("chip_smoke: no src/repro beside this script; run it from the "
             "root of a checkout")
sys.path.insert(0, SRC)

N_REQ, PROMPT, SLOTS, SEG = 8, 256, 4, 2
MIN_AGREEMENT = 0.95
KERNEL = "tpu_custom_call"

_compile_s = [0.0]   # seconds XLA spent compiling (cache hits excluded)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


def _peak(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def phase(name: str, fn):
    """Run ``fn() -> (value, facts)`` and print one line for it."""
    import jax

    c0, t0 = _compile_s[0], time.perf_counter()
    value, facts = fn()
    wall = time.perf_counter() - t0
    dev = jax.devices()[0]
    fields = [f"compile_s={_compile_s[0] - c0:.2f}", f"wall_s={wall:.2f}"]
    fields += [f"{k}={v}" for k, v in facts.items()]
    fields += [f"device_kind={dev.device_kind!r}",
               f"peak_bytes_in_use={_peak(dev)}"]
    print(f"[{name}] " + " ".join(fields), flush=True)
    return value


def agreement(records, ref_top: np.ndarray) -> float:
    """Mean argmax agreement of served logits with the reference; uids
    are submission order, so uid i scored prompt row i - 1."""
    return float(np.mean([np.mean(np.argmax(r.outputs, -1)
                                  == ref_top[uid - 1])
                          for uid, r in records.items()]))


def serve(model, ecfg, tokens, **kw):
    """All requests arrive at t=0 and replay through the scheduler."""
    from repro.launch.scheduler import InflightScheduler
    from repro.launch.workload import Arrival, replay_scheduler

    sched = InflightScheduler(model, ecfg, seg=SEG, **kw)
    rep = replay_scheduler(sched, [Arrival(t=0.0, x=row) for row in tokens])
    recs = {r.uid: r for r in rep.records}
    assert sorted(recs) == list(range(1, len(tokens) + 1)), sorted(recs)
    return sched, recs


def segment_cell_text(sched) -> str:
    """Compiled program of the scheduler's segment cell, lowered on the
    resident state of its (one) pool."""
    (pool,) = sched._pools.values()
    _, _, segment_fn, _ = pool._cells()
    import jax.numpy as jnp
    args = (sched.params, pool._xs_dev, pool.z, jnp.asarray(pool.k),
            jnp.asarray(pool.Ks), jnp.asarray(pool.eps), pool.fs,
            *sched._g_args())
    return segment_fn.lower(*args).compile().as_text()


def check_kernel(sched, on_tpu: bool) -> str:
    if not on_tpu:
        return "skipped(no TPU)"
    assert KERNEL in segment_cell_text(sched), (
        f"no {KERNEL} in the compiled segment cell: the fused hyper_step "
        "kernel is not on the served path")
    return "present"


def argmax_flips_beyond_rounding(got: np.ndarray, want: np.ndarray,
                                 rel: float = 0.02) -> int:
    """Compare two servings of one request's logits (positions x vocab)
    that differ only in how the requests were batched: the values must
    agree within ``rel`` of the logits' scale, and the argmax must agree
    at every position whose top-2 margin exceeds twice the largest
    difference — a flip there is not rounding. Returns the number of
    argmax flips at positions closer to a tie than that.

    Batch composition alone moves bf16 logits on the TPU: one chip's
    pool served at width 8 and at width 2 differed by up to 1% of the
    logit scale and flipped the argmax at near-ties (PERF.md)."""
    diff = np.abs(got - want)
    scale = float(np.max(np.abs(want)))
    assert float(diff.max()) <= rel * scale, (float(diff.max()), scale)
    top2 = np.sort(want, -1)[..., -2:]
    decided = top2[..., 1] - top2[..., 0] > 2 * float(diff.max())
    flip = np.argmax(got, -1) != np.argmax(want, -1)
    assert not (flip & decided).any(), np.flatnonzero(flip & decided)
    return int(flip.sum())


def buckets_of(n_groups: int):
    return tuple(sorted({max(1, n_groups // 4), max(1, n_groups // 2),
                         n_groups}))


def one_chip(cfg, params, tokens, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.engine import EngineConfig, lm_depth_model
    from repro.models.lm import group_layout, lm_forward

    _, n_groups, _ = group_layout(cfg)

    def reference():
        fwd = jax.jit(lambda p, t: jnp.argmax(lm_forward(p, cfg, t)[0], -1))
        top = np.asarray(fwd(params, jnp.asarray(tokens)))
        return top, {"shape": f"{N_REQ}x{PROMPT}x{cfg.vocab}"}

    ref_top = phase("1 reference lm_forward", reference)

    def euler_full_depth():
        model = lm_depth_model(params, cfg, solver="euler", fused=True)
        ecfg = EngineConfig(buckets=(n_groups,), controller="fixed",
                            fixed_K=n_groups, solver="euler", fused=True)
        sched, recs = serve(model, ecfg, tokens, slots=SLOTS)
        agree = agreement(recs, ref_top)
        assert all(r.status == "ok" and r.K == n_groups
                   for r in recs.values())
        assert agree >= MIN_AGREEMENT, (
            f"euler at K={n_groups} agrees {agree} with the discrete "
            f"forward, below {MIN_AGREEMENT}")
        return None, {"K": n_groups, "agreement": f"{agree:.4f}",
                      "kernel": check_kernel(sched, on_tpu)}

    phase(f"2 inflight euler K={n_groups} fused", euler_full_depth)

    buckets = buckets_of(n_groups)
    model = lm_depth_model(params, cfg, solver="hyper_euler", fused=True,
                           refinable=True)
    ecfg = EngineConfig(buckets=buckets, solver="hyper_euler", fused=True)
    served = {}

    def hyper_multirate(overlap: bool):
        def run():
            sched, recs = serve(model, ecfg, tokens, slots=SLOTS,
                                overlap=overlap)
            assert sched.donate == on_tpu, sched.donate
            bad = {u: r.status for u, r in recs.items() if r.status != "ok"}
            assert not bad, f"statuses other than ok: {bad}"
            assert all(np.isfinite(r.outputs).all() for r in recs.values())
            served[overlap] = recs
            facts = {"buckets": ",".join(map(str, buckets)),
                     "K": ",".join(str(recs[u].K) for u in sorted(recs)),
                     "agreement": f"{agreement(recs, ref_top):.4f}"}
            if not overlap:
                facts["kernel"] = check_kernel(sched, on_tpu)
            else:
                sync = served[False]
                same = all(
                    r.K == sync[u].K and r.nfe == sync[u].nfe
                    and np.array_equal(r.outputs, sync[u].outputs)
                    for u, r in recs.items())
                assert same, "overlap completions differ from sync"
                facts["bitwise_equal_to_sync"] = True
            return None, facts
        return run

    phase("3a inflight hyper_euler multirate sync", hyper_multirate(False))
    phase("3b inflight hyper_euler multirate overlap", hyper_multirate(True))

    def kernel_vs_oracle():
        from repro.kernels.hyper_step.ops import fused_rk_update
        from repro.kernels.hyper_step.ref import fused_rk_update_ref

        facts = {}
        shape = (SLOTS, PROMPT, cfg.d_model)
        eps = jnp.asarray([1 / b for b in buckets]
                          + [1.0 / n_groups] * (SLOTS - len(buckets)),
                          jnp.float32)[:SLOTS]
        active = jnp.asarray([1, 1, 0, 1][:SLOTS], jnp.int32)
        for dtype, rtol in ((jnp.bfloat16, 2.0 ** -7), (jnp.float32, 1e-6)):
            k = jax.random.split(jax.random.PRNGKey(3), 3)
            z, dz, g = (jax.random.normal(ki, shape, jnp.float32)
                        .astype(dtype) for ki in k)
            args = (z, (dz,), g, eps, (1.0,), 1)
            got = np.asarray(fused_rk_update(*args, active=active),
                             np.float32)
            want = np.asarray(fused_rk_update_ref(*args, active=active),
                              np.float32)
            np.testing.assert_allclose(got, want, rtol=rtol, atol=1e-6)
            name = jnp.dtype(dtype).name
            facts[f"{name}_exact_frac"] = f"{np.mean(got == want):.6f}"
            if on_tpu:
                text = fused_rk_update.lower(
                    *args, active=active).compile().as_text()
                assert KERNEL in text, f"{name} update compiled without " \
                    f"{KERNEL}"
        facts["kernel"] = "present" if on_tpu else "skipped(no TPU)"
        return None, facts

    phase(f"4 hyper_step kernel vs ref.py at {SLOTS}x{PROMPT}x"
          f"{cfg.d_model}", kernel_vs_oracle)


def four_chips(cfg, key, tokens) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.engine import EngineConfig, lm_depth_model
    from repro.launch.mesh import make_serving_mesh
    from repro.models.lm import group_layout, init_lm

    mesh = make_serving_mesh(4)
    dev0 = jax.devices()[0]

    def init():
        params = jax.jit(init_lm, static_argnums=1,
                         out_shardings=NamedSharding(mesh, PartitionSpec())
                         )(key, cfg)
        jax.block_until_ready(params)
        return params, {"params": "replicated on 4 devices"}

    params = phase("0 init_lm replicated", init)
    # the one-device pool reads device 0's copy in place
    on_dev0 = jax.tree_util.tree_map(
        lambda a: next(s.data for s in a.addressable_shards
                       if s.device == dev0), params)
    _, n_groups, _ = group_layout(cfg)
    ecfg = EngineConfig(buckets=buckets_of(n_groups), solver="hyper_euler",
                        fused=True)
    served = {}

    def pool(mesh_arg, p):
        def run():
            model = lm_depth_model(p, cfg, solver="hyper_euler",
                                   fused=True, refinable=True)
            _, recs = serve(model, ecfg, tokens, slots=2 * SLOTS,
                            mesh=mesh_arg)
            assert all(r.status == "ok" for r in recs.values())
            served[mesh_arg is not None] = recs
            facts = {"slots": 2 * SLOTS}
            if mesh_arg is not None:
                one = served[False]
                diff = flips = 0.0
                for u, r in recs.items():
                    assert (r.K, r.nfe) == (one[u].K, one[u].nfe), u
                    flips += argmax_flips_beyond_rounding(
                        r.outputs, one[u].outputs)
                    diff = max(diff, float(np.max(np.abs(
                        r.outputs - one[u].outputs))))
                facts["K_nfe_equal"] = True
                facts["max_abs_diff"] = f"{diff:.4f}"
                facts["argmax_flips_at_near_ties"] = int(flips)
            return None, facts
        return run

    phase("1 one-device pool", pool(None, on_dev0))
    phase("2 sharded pool mesh=4", pool(mesh, params))
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"[memory {d}] bytes_in_use="
              f"{stats.get('bytes_in_use', 'not reported')} "
              f"peak_bytes_in_use={_peak(d)}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded slot pool against the "
                         "one-device pool")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="rehearse at reduced widths (runs on the CPU; "
                         "never prints a result)")
    args = ap.parse_args()
    if not __debug__:
        sys.exit("chip_smoke: its checks are assertions; run it without -O")

    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax
    import jax.numpy as jnp

    from repro.configs import get
    from repro.models.lm import count_params, init_lm

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not (on_tpu or args.reduced):
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev.platform!r}); nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX sees {len(devices)}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    cfg = get("qwen3_4b")
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), n_layers=8,
                                  dtype="bfloat16", param_dtype="bfloat16")
    print(f"[setup] arch={cfg.name} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} vocab={cfg.vocab} dtype={cfg.dtype} "
          f"requests={N_REQ}x{PROMPT} platform={dev.platform} "
          f"devices={len(devices)} compile_cache={cache_dir}", flush=True)
    key = jax.random.PRNGKey(args.seed)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(args.seed + 1), (N_REQ, PROMPT), 0, cfg.vocab),
        np.int32)

    if args.chips == 4:
        four_chips(cfg, key, tokens)
    else:
        def init():
            params = jax.jit(init_lm, static_argnums=1)(key, cfg)
            jax.block_until_ready(params)
            return params, {"params": count_params(params)}

        params = phase("0 init_lm", init)
        one_chip(cfg, params, tokens, on_tpu)

    if args.reduced:
        print("[done] rehearsal at reduced widths passed; no result is "
              "reported for it")
        return
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
