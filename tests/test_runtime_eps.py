"""Runtime-eps fused serving path, end to end.

The kernel takes step sizes as runtime scalar-prefetch operands
(kernels/hyper_step), so ONE compilation serves every eps pattern —
scalar, traced, per-sample multi-rate — and the controller-driven masked
solve stays fused. This module pins:

  * compile-count: serving many distinct eps values / buckets traces the
    kernel exactly once (the recompile-churn fix);
  * controller-driven fused == unfused, leaf-wise, fp32 and bf16, with and
    without g, with NO fallback warning;
  * the engine packs mixed-K batches into a single fused multi-rate solve
    (one jit cell, outputs matching direct per-K solves);
  * ``Integrator.solve(mesh=...)`` on the CPU debug mesh (subprocess —
    the main test process keeps a single device per the dry-run contract).
"""
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FixedGrid, Integrator, get_tableau
from repro.core.controllers import (
    EmbeddedErrorController, HypersolverResidualController,
)
from repro.kernels.hyper_step.ops import TRACE_COUNTS, fused_rk_update


def _field(s, z):
    return -z * jax.nn.softplus(jnp.mean(z, axis=-1, keepdims=True))


G = lambda eps, s, z, dz: 0.25 * z + 0.1 * dz


# --------------------------------------------------------- compile count ----

def test_kernel_traces_once_across_eps_values():
    """4 different eps buckets through the fused entry point must trace the
    kernel once: eps is a runtime operand, not a specialization key."""
    z = jax.random.normal(jax.random.PRNGKey(0), (4, 40))
    r = jax.random.normal(jax.random.PRNGKey(1), (4, 40))
    fused_rk_update(z, (r,), None, 0.5, (1.0,), 1)  # warm the cache
    before = TRACE_COUNTS["fused_rk_update"]
    for eps in (0.1, 0.125, 0.25, 0.5):
        fused_rk_update(z, (r,), None, eps, (1.0,), 1)
    assert TRACE_COUNTS["fused_rk_update"] == before, (
        "kernel retraced for a new eps value — eps leaked back into the "
        "compilation key")


def test_kernel_traces_once_across_bucket_solves():
    """Serving 4 eps buckets (4 distinct mesh lengths K) through fused
    Integrator solves compiles the kernel once: the scan length changes,
    the kernel shape does not."""
    integ = Integrator(get_tableau("heun"), g=G, fused=True)
    z0 = jax.random.normal(jax.random.PRNGKey(2), (4, 24))
    integ.solve(_field, z0, FixedGrid.over(0.0, 1.0, 3), return_traj=False)
    before = TRACE_COUNTS["fused_rk_update"]
    for K in (2, 4, 8, 16):  # 4 buckets -> 4 distinct scalar eps = 1/K
        integ.solve(_field, z0, FixedGrid.over(0.0, 1.0, K),
                    return_traj=False)
    assert TRACE_COUNTS["fused_rk_update"] == before, (
        "kernel retraced across eps buckets")


# ---------------------------------------- controller-driven fused solve ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_g", [True, False])
def test_controlled_solve_fused_matches_unfused(dtype, with_g):
    """The controller's masked multi-rate solve (per-sample eps rows) takes
    the kernel path with NO fallback warning and matches the jnp path
    leaf-wise — fp32 atol 1e-6, bf16 at storage precision."""
    g = G if with_g else None
    ctrl = (HypersolverResidualController(tol=1e-3, k_min=1, k_max=8)
            if with_g else EmbeddedErrorController(tol=1e-3, k_min=1,
                                                   k_max=8))
    z0 = jax.random.normal(jax.random.PRNGKey(3), (6, 33)).astype(dtype)
    grid = FixedGrid.over(0.0, 1.0, 8)
    res_u, st_u = Integrator(get_tableau("heun"), g=g).solve(
        _field, z0, grid, return_traj=False, controller=ctrl)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        res_f, st_f = Integrator(get_tableau("heun"), g=g, fused=True).solve(
            _field, z0, grid, return_traj=False, controller=ctrl)
    assert res_f.dtype == z0.dtype
    np.testing.assert_array_equal(np.asarray(st_u.K), np.asarray(st_f.K))
    np.testing.assert_array_equal(np.asarray(st_u.nfe), np.asarray(st_f.nfe))
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(res_f, np.float32),
                               np.asarray(res_u, np.float32), **tol)
    # multi-rate actually happened: controller spread the mesh lengths
    assert len(np.unique(np.asarray(st_f.K))) >= 1


@pytest.mark.parametrize("with_g", [True, False])
def test_solve_multirate_fused_matches_per_sample_solves(with_g):
    """solve_multirate with an explicit mixed-K row == stacking per-sample
    fixed-grid solves at each K_i, on the fused path, for a pytree state."""
    g = (lambda eps, s, z, dz: jax.tree_util.tree_map(
        lambda l: 0.2 * l, z)) if with_g else None

    def f(s, state):
        z, aux = state
        k = jax.nn.softplus(jnp.mean(aux, axis=-1))[:, None, None]
        return (-z * k, -0.5 * aux)

    B = 4
    z0 = (jax.random.normal(jax.random.PRNGKey(4), (B, 3, 7)),
          jax.random.normal(jax.random.PRNGKey(5), (B, 2)))
    Ks = jnp.asarray([1, 2, 5, 8], jnp.int32)
    integ = Integrator(get_tableau("midpoint"), g=g, fused=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        out = integ.solve_multirate(f, z0, (0.0, 1.0), Ks, 8)
    for i in range(B):
        zi = jax.tree_util.tree_map(lambda l: l[i:i + 1], z0)
        ref = integ.solve(f, zi, FixedGrid.over(0.0, 1.0, int(Ks[i])),
                          return_traj=False)
        for lo, lr in zip(jax.tree_util.tree_leaves(out),
                          jax.tree_util.tree_leaves(ref)):
            np.testing.assert_allclose(np.asarray(lo[i]), np.asarray(lr[0]),
                                       rtol=1e-6, atol=1e-6)


def test_solve_multirate_rejects_truncating_k_max():
    """A concrete Ks row exceeding k_max would silently stop mid-span —
    the entry point refuses it (traced callers own the invariant)."""
    integ = Integrator(get_tableau("euler"), fused=True)
    z0 = jnp.ones((2, 4))
    with pytest.raises(ValueError, match="truncates"):
        integ.solve_multirate(_field, z0, (0.0, 1.0),
                              jnp.asarray([4, 16]), 8)


# ------------------------------------------------------- engine mixed-K ----

def _toy_model(fused=False):
    from repro.launch.engine import DepthModel

    def field_of(p, x):
        k = jax.nn.softplus(jnp.mean(x, axis=-1, keepdims=True))
        return lambda s, z: -z * k

    return DepthModel(
        embed=lambda p, x: x + 0.0,
        field_of=field_of,
        readout=lambda p, x, zT: zT,
        integ=Integrator(tableau=get_tableau("euler"), fused=fused),
    )


def test_engine_packs_mixed_K_into_one_fused_cell():
    """Requests landing in different buckets pack into ONE batch and ONE
    jit cell (mixed-K masked multi-rate solve), fused, with outputs equal
    to direct per-K solves."""
    from repro.launch.engine import EngineConfig, MultiRateEngine

    rng = np.random.RandomState(0)
    easy = rng.randn(3, 4).astype(np.float32) * 0.05 - 2.0
    hard = rng.randn(3, 4).astype(np.float32) * 0.05 + 3.0
    xs = np.concatenate([easy, hard])
    eng = MultiRateEngine(_toy_model(fused=True),
                          EngineConfig(buckets=(2, 4, 8, 16), tol=5e-3,
                                       max_batch=8, fused=True))
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        res = eng.run(xs)
    assert len({r.K for r in res}) > 1, "bucket mix expected"
    assert len(eng._solve_fns) == 1, (
        "mixed-K batch should be served by a single (shape, k_max) cell, "
        f"got {list(eng._solve_fns)}")
    assert all(r.fused_kernel for r in res)
    model = _toy_model()
    for i, r in enumerate(res):
        x = jnp.asarray(xs[i:i + 1])
        direct = model.integ.solve(model.field_of(None, x),
                                   model.embed(None, x),
                                   FixedGrid.over(0.0, 1.0, r.K),
                                   return_traj=False)
        np.testing.assert_allclose(np.asarray(r.outputs),
                                   np.asarray(direct[0]), rtol=1e-6,
                                   atol=1e-6)


# ------------------------------------------------------- sharded solve ----

_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import FixedGrid, Integrator, get_tableau
    from repro.core.controllers import EmbeddedErrorController
    from repro.launch.mesh import make_debug_mesh, sharded_solve

    assert jax.device_count() == 4, jax.device_count()
    mesh = make_debug_mesh(n_data=2, n_model=2)
    f = lambda s, z: -z * jnp.tanh(jnp.mean(z, -1, keepdims=True) + 2.0)
    z0 = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    integ = Integrator(get_tableau("heun"), fused=True)

    grid = FixedGrid.over(0.0, 1.0, 4)
    ref = integ.solve(f, z0, grid, return_traj=False)
    out = sharded_solve(integ, f, z0, grid, mesh=mesh, return_traj=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    print("SHARDED_PLAIN_OK")

    eps = jnp.linspace(0.1, 0.25, 8)
    gb = FixedGrid(0.0, eps, 4)
    out_b = sharded_solve(integ, f, z0, gb, mesh=mesh, return_traj=False)
    ref_b = integ.solve(f, z0, gb, return_traj=False)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(ref_b),
                               rtol=1e-6, atol=1e-6)
    print("SHARDED_BATCHED_EPS_OK")

    ctrl = EmbeddedErrorController(tol=1e-3, k_min=1, k_max=8)
    res, st = integ.solve(f, z0, FixedGrid.over(0.0, 1.0, 8),
                          return_traj=False, controller=ctrl, mesh=mesh)
    res_r, st_r = integ.solve(f, z0, FixedGrid.over(0.0, 1.0, 8),
                              return_traj=False, controller=ctrl)
    np.testing.assert_allclose(np.asarray(res), np.asarray(res_r),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(st.K), np.asarray(st_r.K))
    np.testing.assert_array_equal(np.asarray(st.nfe), np.asarray(st_r.nfe))
    assert st.probe_nfe == st_r.probe_nfe
    print("SHARDED_CONTROLLER_OK")

    try:
        sharded_solve(integ, f, z0[:3], grid, mesh=mesh, return_traj=False)
    except ValueError as e:
        assert "does not divide" in str(e), e
        print("SHARDED_DIVISIBILITY_OK")
""")


def test_sharded_solve_debug_mesh_subprocess():
    """Integrator.solve(mesh=) on a forced 4-device CPU mesh: plain,
    batched-eps, and controller-driven solves all match the single-device
    results shard-for-shard (subprocess — the main test process must keep
    one device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    for marker in ("SHARDED_PLAIN_OK", "SHARDED_BATCHED_EPS_OK",
                   "SHARDED_CONTROLLER_OK", "SHARDED_DIVISIBILITY_OK"):
        assert marker in out, (marker, out[-4000:])
