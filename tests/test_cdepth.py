"""Continuous-depth LM mode: exact equivalence at K = n_groups, NFE/error
pareto with the hypersolver at K < n_groups."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models.cdepth import (
    cdepth_residual_loss, discrete_depth_trajectory, lm_forward_cdepth,
    lm_g_init,
)
from repro.models.lm import group_layout, init_lm, lm_forward
from repro.optim import adamw, apply_updates, clip_by_global_norm

ARCH = "qwen3_4b"  # dense, homogeneous pattern


def _setup(n_layers=8):
    import dataclasses
    cfg = dataclasses.replace(get(ARCH).reduced(), n_layers=n_layers)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    return cfg, params, toks


def test_euler_full_K_equals_discrete_forward():
    """Euler at K = n_groups must reproduce the discrete network exactly."""
    cfg, params, toks = _setup()
    _, n_groups, _ = group_layout(cfg)
    ref, _ = lm_forward(params, cfg, toks)
    ode = lm_forward_cdepth(params, cfg, toks, K=n_groups, solver="euler")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ode), rtol=1e-5,
                               atol=1e-5)


def test_reduced_K_degrades_then_hypersolver_recovers():
    cfg, params, toks = _setup(n_layers=8)
    _, n_groups, _ = group_layout(cfg)
    K = n_groups // 2

    ref, _ = lm_forward(params, cfg, toks)
    base = lm_forward_cdepth(params, cfg, toks, K=K, solver="euler")
    err_base = float(jnp.mean(jnp.abs(ref - base)))
    assert err_base > 0  # skipping layers must change the output

    gp = lm_g_init(jax.random.PRNGKey(2), cfg, rank=32,
                   param_dtype=jnp.float32)
    opt = adamw(3e-3)
    st = opt.init(gp)

    @jax.jit
    def fit(gp, st, i, toks):
        loss, grads = jax.value_and_grad(
            lambda g: cdepth_residual_loss(params, g, cfg, toks, K))(gp)
        grads, _ = clip_by_global_norm(grads, 1.0)
        upd, st = opt.update(grads, st, gp, i)
        return apply_updates(gp, upd), st, loss

    key = jax.random.PRNGKey(3)
    losses = []
    for i in range(120):
        if i % 10 == 0:
            key, sub = jax.random.split(key)
            batch = jax.random.randint(sub, (2, 8), 0, cfg.vocab)
        gp, st, loss = fit(gp, st, i, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], (losses[0], losses[-1])

    hyper = lm_forward_cdepth(params, cfg, toks, K=K, solver="euler",
                              g_params=gp)
    err_hyper = float(jnp.mean(jnp.abs(ref - hyper)))
    assert err_hyper < err_base, (err_base, err_hyper)


def test_trajectory_shapes():
    cfg, params, toks = _setup(n_layers=4)
    _, n_groups, _ = group_layout(cfg)
    traj = discrete_depth_trajectory(params, cfg, toks)
    assert traj.shape[0] == n_groups + 1
    assert np.all(np.isfinite(np.asarray(traj)))


def _nonsplat_constant_types(hlo_text: str):
    """Tensor types of the non-splat ``stablehlo.constant`` ops of a
    lowered module — where a closed-over weight array would land."""
    import re
    return {m.group(2) for m in re.finditer(
        r"stablehlo\.constant dense<(\"0x[0-9A-F]+\"|\[[^>]*)>\s*:\s*"
        r"tensor<([0-9x]+x[a-z0-9]+)>", hlo_text)}


def test_serving_cells_take_weights_as_operands():
    """REGRESSION: the segment, probe and readout cells of an LM
    ``DepthModel`` take its weights as traced operands. No constant of
    a weight's shape is compiled in (at published widths that constant
    would be GBs inside the program), and each cell's arguments cover
    the weights it reads."""
    import dataclasses

    from repro.launch.engine import EngineConfig, lm_depth_model
    from repro.launch.scheduler import InflightScheduler

    cfg = dataclasses.replace(get(ARCH).reduced(), n_layers=4)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    model = lm_depth_model(params, cfg, solver="hyper_euler", fused=True,
                           refinable=True)
    sched = InflightScheduler(
        model, EngineConfig(buckets=(2, 4), solver="hyper_euler",
                            fused=True), slots=2, seg=2)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (3, 8), 0,
                                         cfg.vocab))
    assert len(sched.run(toks)) == 3
    (pool,) = sched._pools.values()
    probe, _, segment, readout = pool._cells()
    gp = sched.g_params
    lowered = {
        "segment": segment.lower(
            sched.params, pool._xs_dev, pool.z, jnp.asarray(pool.k),
            jnp.asarray(pool.Ks), jnp.asarray(pool.eps), pool.fs, gp),
        "probe": probe.lower(sched.params, pool._xs_dev, gp),
        "readout": readout.lower(sched.params, pool._xs_dev, pool.z),
    }
    nbytes = lambda t: sum(l.nbytes for l in jax.tree_util.tree_leaves(t))
    reads = {"segment": nbytes(params["groups"]),
             "probe": nbytes(params["groups"]) + nbytes(params["embed"]),
             "readout": nbytes(params["embed"])}
    weight_types = {
        "x".join(map(str, l.shape)) + "x" + l.dtype.name.replace(
            "float", "f").replace("bfloat16", "bf16")
        for l in jax.tree_util.tree_leaves(params) if l.size > 1}
    for name, low in lowered.items():
        consts = _nonsplat_constant_types(low.as_text())
        assert not consts & weight_types, (name, consts & weight_types)
        args = low.compile().memory_analysis().argument_size_in_bytes
        assert args >= reads[name], (name, args, reads[name])


def test_constant_scan_finds_a_closed_over_weight():
    """The scan above is not vacuous: a weight closed over by a jit
    cell shows up as a constant of its shape."""
    w = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    text = jax.jit(lambda x: x @ w).lower(jnp.ones((2, 8))).as_text()
    assert "8x16xf32" in _nonsplat_constant_types(text)
