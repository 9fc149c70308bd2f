"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3_4b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/run1 [--resume]

Loop skeleton (the piece that matters at 1000 nodes):
  restore-latest -> data skip-ahead -> step loop under a watchdog ->
  periodic async checkpoints -> on failure: bounded restore-and-retry.
Works on CPU with reduced configs; the same code drives the production
mesh when devices exist (mesh/microbatching/sharding all flow from
launch/steps.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get
from repro.data import ShardedLoader, token_batches
from repro.distributed.fault import (
    FailureInjector, StepFailure, StepWatchdog, WatchdogConfig,
)
from repro.launch.mesh import auto_mesh
from repro.launch.steps import StepSettings, make_train_step
from repro.models.lm import init_lm
from repro.models import encdec as whisper

log = logging.getLogger("repro.train")


def train_loop(
    cfg,
    settings: StepSettings,
    mesh,
    steps: int,
    batch_iter,
    ckpt: Optional[CheckpointManager] = None,
    ckpt_every: int = 25,
    injector: Optional[FailureInjector] = None,
    watchdog: Optional[StepWatchdog] = None,
    seed: int = 0,
):
    """Returns (params, opt_state, history). Restartable: if ``ckpt`` has a
    latest step, resumes from it (params, opt state, step index)."""
    step_fn, opt, (a_params, a_opt, p_sh, o_sh) = make_train_step(
        cfg, settings, mesh)
    watchdog = watchdog or StepWatchdog(WatchdogConfig())

    start = 0
    params = opt_state = None
    if ckpt is not None:
        latest = ckpt.latest_step()
        if latest is not None:
            state = ckpt.restore(latest, {"params": a_params, "opt": a_opt},
                                 {"params": p_sh, "opt": o_sh})
            params, opt_state = state["params"], state["opt"]
            start = latest
            log.info("resumed from step %d", latest)
    if params is None:
        init = (whisper.init_encdec if cfg.is_encdec else init_lm)
        with jax.set_mesh(mesh):
            params = jax.jit(
                lambda k: init(k, cfg), out_shardings=p_sh
            )(jax.random.PRNGKey(seed))
            opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)

    history = []
    it = iter(batch_iter)
    # data skip-ahead keeps the stream aligned with the resumed step
    for _ in range(start):
        next(it)

    step = start
    while step < steps:
        batch = next(it)
        try:
            if injector is not None:
                injector.maybe_fail(step)
            # the watchdog owns the NaN screen (WatchdogConfig.
            # nan_is_failure): loss_of names the scalar to vet
            params, opt_state, metrics = watchdog.run(
                step_fn, params, opt_state, jnp.asarray(step, jnp.int32),
                batch, loss_of=lambda out: out[2]["loss"])
            loss = float(metrics["loss"])
        except StepFailure as e:
            log.warning("step %d failed: %s", step, e)
            if ckpt is None or not watchdog.record_failure():
                raise
            latest = ckpt.latest_step()
            if latest is None:
                raise StepFailure("no checkpoint to restore from") from e
            state = ckpt.restore(latest, {"params": a_params, "opt": a_opt},
                                 {"params": p_sh, "opt": o_sh})
            params, opt_state = state["params"], state["opt"]
            # rewind the data stream to the restored step
            it = iter(batch_iter)
            for _ in range(latest):
                next(it)
            step = latest
            continue
        history.append({"step": step, "loss": loss,
                        "grad_norm": float(metrics["grad_norm"])})
        step += 1
        if ckpt is not None and (step % ckpt_every == 0 or step == steps):
            ckpt.save(step, {"params": params, "opt": opt_state})
    if ckpt is not None:
        ckpt.wait()
    return params, opt_state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)

    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = auto_mesh((1, 1), ("data", "model")) \
        if jax.device_count() == 1 else None
    assert mesh is not None, "production launch requires a real device mesh"
    settings = StepSettings(microbatches=args.microbatches, remat="none",
                            lr=args.lr, zero_opt=False)

    batches = ({"tokens": t, "targets": y}
               for t, y in token_batches(cfg.vocab, args.batch, args.seq))
    ckpt = CheckpointManager(args.ckpt_dir, keep=3, async_save=True) \
        if args.ckpt_dir else None

    t0 = time.time()
    params, _, hist = train_loop(cfg, settings, mesh, args.steps,
                                 batches, ckpt, args.ckpt_every)
    for h in hist[::args.log_every] + hist[-1:]:
        print(f"step {h['step']:5d}  loss {h['loss']:.4f}  "
              f"gnorm {h['grad_norm']:.3f}")
    print(f"total {time.time() - t0:.1f}s; final loss {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
