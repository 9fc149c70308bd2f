"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a serving cell can have, and for the control: the
reference computed in float8 in the program's place. A CPU rehearsal of
the tiny cell, in this process, with the program patched."""
import jax.numpy as jnp
import pytest

import check
from tiny import copy_with_tiny_cell


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return copy_with_tiny_cell(str(tmp_path_factory.mktemp("faults")))


def _run(bench, seed=5, control=False, cell="tiny"):
    import run

    return run.run(cell, seed, 2.0, False, rehearse=True,
                   bench_dir=bench, control=control)


def _numbers(res):
    return {k: v["value"] for k, v in res["checks"].items()}


def test_sound_run_is_correct(bench):
    res = _run(bench)
    assert res["correct"] is True, res["checks"]


def test_state_left_unchanged(bench, monkeypatch):
    from repro.core.integrate import Integrator

    def step(self, f, s, eps, z, first_stage=None, active=None):
        return z, None, None

    monkeypatch.setattr(Integrator, "step", step)
    res = _run(bench)
    assert res["correct"] is False
    assert _numbers(res)["max_logit_gap"] > 1.0


def _patch_g(monkeypatch, alter):
    from repro.models import cdepth

    orig = cdepth.lm_g_apply
    monkeypatch.setattr(
        cdepth, "lm_g_apply",
        lambda gp, eps, s, x, h, dh: alter(eps, orig(gp, eps, s, x, h, dh)))


def test_correction_left_out_of_the_update(bench, monkeypatch):
    # the hypersolver's eps^2 g term dropped from every step, while the
    # probe (one step over the whole depth, eps = 1) still reads g
    def probe_only(eps, g):
        keep = jnp.asarray(eps) >= 1
        keep = jnp.reshape(keep, keep.shape + (1,) * (g.ndim - keep.ndim))
        return jnp.where(keep, g, jnp.zeros_like(g))

    _patch_g(monkeypatch, probe_only)
    res = _run(bench)
    assert res["correct"] is False
    assert _numbers(res)["k_mismatch"] == 0
    assert _numbers(res)["logit_rel_err"] > res["checks"]["logit_rel_err"][
        "limit"]


def test_probe_blind_to_the_correction(bench, monkeypatch):
    # g read as 0 everywhere: the probe falls to the smallest bucket
    _patch_g(monkeypatch, lambda eps, g: jnp.zeros_like(g))
    res = _run(bench)
    assert res["correct"] is False
    assert _numbers(res)["k_mismatch"] > 0


def _patch_readout(monkeypatch, alter):
    from repro.launch.scheduler import _SlotPool

    orig = _SlotPool._readout_finished
    monkeypatch.setattr(_SlotPool, "_readout_finished",
                        lambda self, idx: alter(orig(self, idx)))


def test_answer_altered_where_produced(bench, monkeypatch):
    # one position of every answer flipped in sign: its argmax becomes the
    # reference's least likely token
    _patch_readout(monkeypatch, lambda o: o.at[:, 1, :].set(-o[:, 1, :]))
    res = _run(bench)
    assert res["correct"] is False
    assert _numbers(res)["max_logit_gap"] > 1.0


def test_half_of_the_batch_left_out(bench, monkeypatch):
    # the second half of each retiring batch answered with the first
    # half's rows, as a gather that leaves half the rows out would
    def half(o):
        n = o.shape[0] // 2
        return o if n == 0 else jnp.concatenate([o[:n], o[:o.shape[0] - n]])

    _patch_readout(monkeypatch, half)
    res = _run(bench)
    assert res["correct"] is False


def _control_fails(res):
    assert res["correct"] is True
    limits = {k: v["limit"] for k, v in res["checks"].items()}
    ctl = dict(_numbers(res))
    ctl.update({k[len("control_"):]: v for k, v in res["control"].items()})
    assert not check.verdict(ctl, limits), ctl


def test_control_in_lower_precision_is_not_correct(bench):
    _control_fails(_run(bench, control=True))


def test_expert_half_left_out(bench, monkeypatch):
    # the moe layers' routed half dropped from the program: the check runs
    # the configuration's own layout, whose reference keeps it
    from repro.models import lm

    orig = lm.moe_apply_sorted

    def no_experts(params, x, **kw):
        out = orig(params, x, **kw)
        return out._replace(y=jnp.zeros_like(out.y))

    monkeypatch.setattr(lm, "moe_apply_sorted", no_experts)
    res = _run(bench, cell="tiny_moe")
    assert res["correct"] is False
    assert _numbers(res)["logit_rel_err"] > res["checks"]["logit_rel_err"][
        "limit"]


def test_control_of_a_layout_that_is_not_dense(bench):
    _control_fails(_run(bench, control=True, cell="tiny_moe"))
