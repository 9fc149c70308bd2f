"""End-to-end fault tolerance: a training run with injected failures must
resume from checkpoints and converge to the same trajectory as an
uninterrupted run (bitwise-identical data stream via skip-ahead)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get
from repro.data import token_batches
from repro.distributed.fault import FailureInjector, StepWatchdog, WatchdogConfig
from repro.launch.mesh import make_debug_mesh
from repro.launch.steps import StepSettings
from repro.launch.train import train_loop


def _mesh():
    return make_debug_mesh(n_data=1, n_model=1)


def _batches(cfg, n=None):
    def gen():
        it = token_batches(cfg.vocab, 4, 32, seed=5)
        for t, y in it:
            yield {"tokens": t, "targets": y}
    return gen()


class _Replayable:
    """iterable whose iter() restarts the deterministic stream."""

    def __init__(self, cfg):
        self.cfg = cfg

    def __iter__(self):
        it = token_batches(self.cfg.vocab, 4, 32, seed=5)
        return ({"tokens": t, "targets": y} for t, y in it)


@pytest.fixture(scope="module")
def setup():
    cfg = get("qwen3_4b").reduced()
    settings = StepSettings(microbatches=1, remat="none", zero_opt=False,
                            lr=1e-3)
    return cfg, settings


@pytest.mark.slow  # three full train loops with restarts: long-JIT
def test_recovers_from_injected_failures(tmp_path, setup):
    cfg, settings = setup
    mesh = _mesh()
    # uninterrupted baseline
    _, _, hist_ref = train_loop(cfg, settings, mesh, steps=12,
                                batch_iter=_Replayable(cfg), ckpt=None)
    # failure-injected run with checkpoints every 4 steps
    ckpt = CheckpointManager(str(tmp_path / "ft"), keep=3)
    inj = FailureInjector(fail_at=(6, 9))
    wd = StepWatchdog(WatchdogConfig(max_restarts=5))
    _, _, hist = train_loop(cfg, settings, mesh, steps=12,
                            batch_iter=_Replayable(cfg), ckpt=ckpt,
                            ckpt_every=4, injector=inj, watchdog=wd)
    assert wd.restarts == 2
    ref = {h["step"]: h["loss"] for h in hist_ref}
    got = {h["step"]: h["loss"] for h in hist}
    assert set(got) == set(ref)
    for s in ref:
        np.testing.assert_allclose(got[s], ref[s], rtol=1e-4), s


def test_restart_budget_exhausted_raises(tmp_path, setup):
    cfg, settings = setup
    from repro.distributed.fault import StepFailure
    ckpt = CheckpointManager(str(tmp_path / "budget"), keep=2)
    inj = FailureInjector(fail_at=(2,))

    class AlwaysFail(FailureInjector):
        def maybe_fail(self, step):
            if step == 2:
                raise StepFailure("permanent")

    wd = StepWatchdog(WatchdogConfig(max_restarts=2))
    with pytest.raises(StepFailure):
        train_loop(cfg, settings, _mesh(), steps=5,
                   batch_iter=_Replayable(cfg), ckpt=ckpt, ckpt_every=1,
                   injector=AlwaysFail(), watchdog=wd)


def test_elastic_restore_across_mesh_shapes(tmp_path, setup):
    """Checkpoint written under one mesh restores under another (here 1x1
    CPU both ways, exercising the device_put-per-leaf path; on hardware the
    same call re-shards 512->256)."""
    cfg, settings = setup
    mesh = _mesh()
    ckpt = CheckpointManager(str(tmp_path / "elastic"), keep=2)
    _, _, h1 = train_loop(cfg, settings, mesh, steps=4,
                          batch_iter=_Replayable(cfg), ckpt=ckpt,
                          ckpt_every=2)
    # "new cluster": fresh mesh + loop resuming from the checkpoint
    mesh2 = _mesh()
    _, _, h2 = train_loop(cfg, settings, mesh2, steps=8,
                          batch_iter=_Replayable(cfg), ckpt=ckpt,
                          ckpt_every=4)
    assert h2[0]["step"] == 4  # resumed, not restarted
    assert len(h2) == 4
