"""Multi-rate serving engine (launch/engine.py): bucket assignment,
request-queue packing, per-request NFE accounting, fixed-vs-multirate
consistency, and the LM adapter end-to-end."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FixedGrid, Integrator, get_tableau
from repro.launch.engine import (
    DepthModel, EngineConfig, MultiRateEngine, lm_depth_model,
    snap_to_buckets,
)


# ----------------------------------------------------------- bucket policy ----

def test_snap_to_buckets():
    Ks = np.array([1, 2, 3, 4, 5, 8])
    np.testing.assert_array_equal(snap_to_buckets(Ks, (2, 4, 8)),
                                  [2, 2, 4, 4, 8, 8])
    np.testing.assert_array_equal(snap_to_buckets(Ks, (16,)), [16] * 6)
    with pytest.warns(RuntimeWarning):  # overshoot clamps down, warned
        np.testing.assert_array_equal(
            snap_to_buckets(np.array([9, 40]), (2, 4, 8)), [8, 8])


def test_snap_to_buckets_overflow_clamps_with_one_time_warning():
    """A probed K above the largest configured bucket clamps to
    buckets[-1] — integrating COARSER than asked — and says so once (the
    latch is re-armed per test by conftest)."""
    with pytest.warns(RuntimeWarning, match="exceeds the largest"):
        out = snap_to_buckets(np.array([3, 40]), (2, 4, 8))
    np.testing.assert_array_equal(out, [4, 8])
    # one-time: the second overflow in the same process stays silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        np.testing.assert_array_equal(
            snap_to_buckets(np.array([99]), (2, 4, 8)), [8])
    # in-range snapping never warns
    from repro.launch.engine import reset_snap_overflow_warning

    reset_snap_overflow_warning()
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        snap_to_buckets(np.array([1, 8]), (2, 4, 8))


def test_engine_config_requires_sorted_buckets():
    with pytest.raises(AssertionError):
        EngineConfig(buckets=(8, 2, 4))


# -------------------------------------------------------- synthetic model ----

def _toy_model(g_scale=None, solver="euler"):
    """A tiny servable 'model': z' = -z * softplus(mean(x)); request
    difficulty is controlled directly by the input magnitude."""
    tab = get_tableau(solver[len("hyper_"):] if solver.startswith("hyper_")
                      else solver)
    g = None
    if g_scale is not None:
        g = lambda eps, s, z, dz: g_scale * z
    stiff = lambda x: jax.nn.softplus(jnp.mean(x, axis=-1, keepdims=True))

    def field_of(p, x):
        k = stiff(x)
        return lambda s, z: -z * k

    return DepthModel(
        embed=lambda p, x: x + 0.0,
        field_of=field_of,
        readout=lambda p, x, zT: zT,
        integ=Integrator(tableau=tab, g=g),
    )


def _requests(n=10, d=4, seed=0):
    # the shared difficulty-mix generator, un-interleaved so the first
    # half is the easy (softplus(-2) small) slice the assertions key on
    from repro.launch.workload import heterogeneous_requests

    return heterogeneous_requests(n, d, seed=seed, interleave=False)


# ------------------------------------------------------------------ engine ----

def test_engine_orders_results_and_accounts_nfe():
    model = _toy_model()
    eng = MultiRateEngine(model, EngineConfig(buckets=(2, 4, 8), tol=1e-2,
                                              max_batch=3))
    xs = _requests(9)
    res = eng.run(xs)
    assert [r.uid for r in res] == sorted(r.uid for r in res)
    assert len(res) == 9 and len(eng) == 0
    for r in res:
        assert r.K in (2, 4, 8)
        # embedded HEUN probe (2 evals, 1 reused) + euler K evals
        assert r.nfe == eng.probe_nfe + r.K
        assert r.err_probe > 0.0
    assert eng.probe_nfe == 1  # 2-stage probe minus the reused dz


def test_engine_routes_hard_requests_to_finer_buckets():
    model = _toy_model()
    eng = MultiRateEngine(model, EngineConfig(buckets=(2, 4, 8, 16),
                                              tol=5e-3, max_batch=8))
    xs = _requests(12)
    res = eng.run(xs)
    k_easy = [r.K for r in res[:6]]
    k_hard = [r.K for r in res[6:]]
    assert max(k_easy) <= min(k_hard), (k_easy, k_hard)
    assert min(k_easy) < max(k_hard), "buckets should actually differ"


def test_engine_outputs_match_direct_solve():
    """Engine-served outputs == a direct fixed-grid solve at the same K
    (packing and probe reuse change nothing numerically)."""
    model = _toy_model()
    eng = MultiRateEngine(model, EngineConfig(buckets=(2, 4, 8), tol=1e-2,
                                              max_batch=4))
    xs = _requests(6)
    res = eng.run(xs)
    for i, r in enumerate(res):
        x = jnp.asarray(xs[i:i + 1])
        direct = model.integ.solve(model.field_of(None, x),
                                   model.embed(None, x),
                                   FixedGrid.over(0.0, 1.0, r.K),
                                   return_traj=False)
        np.testing.assert_allclose(np.asarray(r.outputs),
                                   np.asarray(direct[0]), rtol=1e-6)


def test_engine_fixed_controller_is_fixed_k():
    model = _toy_model()
    eng = MultiRateEngine(model, EngineConfig(buckets=(4,),
                                              controller="fixed", fixed_K=4))
    res = eng.run(_requests(5))
    assert all(r.K == 4 for r in res)
    assert all(r.nfe == 4 for r in res)       # no probe on the fixed path
    assert all(r.err_probe == 0.0 for r in res)
    assert eng.probe_nfe == 0


def test_engine_residual_controller_with_g():
    model = _toy_model(g_scale=0.3, solver="hyper_euler")
    eng = MultiRateEngine(model, EngineConfig(buckets=(2, 4, 8), tol=1e-1,
                                              solver="hyper_euler"))
    res = eng.run(_requests(6))
    assert type(eng.controller).__name__ == "HypersolverResidualController"
    assert eng.probe_nfe == 0                 # 1-eval probe, fully reused
    assert all(r.nfe == r.K for r in res)     # probe is free for HyperEuler


def test_engine_hyper_solver_requires_g():
    model = _toy_model()                       # no correction bound
    with pytest.raises(ValueError):
        MultiRateEngine(model, EngineConfig(solver="hyper_euler"))


def test_engine_groups_mixed_shapes():
    model = _toy_model()
    eng = MultiRateEngine(model, EngineConfig(buckets=(2, 4), tol=1e-2))
    uid_a = eng.submit(np.zeros(3, np.float32) - 2.0)
    uid_b = eng.submit(np.zeros(5, np.float32) - 2.0)
    done = eng.step()
    assert sorted(c.uid for c in done) == [uid_a, uid_b]
    shapes = {c.uid: c.outputs.shape for c in done}
    assert shapes[uid_a] == (3,) and shapes[uid_b] == (5,)


# -------------------------------------------------------------- LM adapter ----

def test_lm_engine_end_to_end():
    """The LM depth model serves through the engine; a fixed-K engine run
    reproduces lm_forward_cdepth at the same K, and per-request stats are
    threaded through (models/cdepth.py SolveStats counterpart)."""
    from repro.configs import get
    from repro.models.cdepth import lm_forward_cdepth
    from repro.models.lm import init_lm

    cfg = dataclasses.replace(get("qwen3_4b").reduced(), n_layers=4,
                              vocab=64)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (3, 6), 0,
                                         cfg.vocab), np.int32)

    model = lm_depth_model(params, cfg, solver="euler")
    eng = MultiRateEngine(model, EngineConfig(buckets=(2,),
                                              controller="fixed", fixed_K=2,
                                              max_batch=2))
    res = eng.run(toks)
    ref, stats = lm_forward_cdepth(params, cfg, jnp.asarray(toks), K=2,
                                   solver="euler", with_stats=True)
    np.testing.assert_array_equal(np.asarray(stats.nfe), [2, 2, 2])
    for i, r in enumerate(res):
        assert r.nfe == int(stats.nfe[i])
        np.testing.assert_allclose(np.asarray(r.outputs),
                                   np.asarray(ref[i]), rtol=2e-4, atol=2e-4)

    # multi-rate path with the embedded probe stays in the bucket set
    eng2 = MultiRateEngine(model, EngineConfig(buckets=(1, 2, 4), tol=1e3))
    res2 = eng2.run(toks)
    assert all(r.K in (1, 2, 4) for r in res2)

    # the models-layer probe API is the same selection the engine runs
    from repro.models.cdepth import depth_probe

    probe = depth_probe(params, cfg, jnp.asarray(toks), eng2.controller,
                        solver="euler")
    raw_k, raw_err = eng2.probe(toks)
    np.testing.assert_array_equal(np.asarray(probe.K), raw_k)
    np.testing.assert_allclose(np.asarray(probe.err), raw_err, rtol=1e-6)


@pytest.mark.parametrize("loop", ["engine", "inflight"])
def test_node_depth_model_serves_with_weights_as_operands(loop):
    """``node_depth_model`` follows the weights-as-operands contract: the
    NeuralODE's params ride the cells, and both serving loops reproduce
    the NeuralODE's own fixed-K forward."""
    from repro.core.neural_ode import NeuralODE
    from repro.launch.engine import node_depth_model
    from repro.launch.scheduler import InflightScheduler

    node = NeuralODE(
        f_apply=lambda p, s, x, z: -z * jax.nn.softplus(p["k"]),
        hx_apply=lambda p, x: x @ p["win"],
        hy_apply=lambda p, z: z @ p["wout"])
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {"k": jax.random.normal(k1, (8,)),
              "win": jax.random.normal(k2, (5, 8)),
              "wout": jax.random.normal(k3, (8, 3))}
    model = node_depth_model(node, params, solver="euler")
    assert model.params is params
    ecfg = EngineConfig(buckets=(4,), controller="fixed", fixed_K=4)
    xs = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (6, 5)))
    serving = MultiRateEngine(model, ecfg) if loop == "engine" else \
        InflightScheduler(model, ecfg, slots=4, seg=2)
    res = serving.run(xs)
    ref = node.forward(params, jnp.asarray(xs), "euler", 4)
    for i, r in enumerate(res):
        np.testing.assert_allclose(np.asarray(r.outputs),
                                   np.asarray(ref[i]), rtol=1e-5,
                                   atol=1e-5)
