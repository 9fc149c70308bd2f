"""Compiles for a described TPU v5e, at the served widths, with no chip.

The TPU compiler is installed beside the CPU backend: it compiles for a
``v5e:2x2`` topology that is described, not attached. That refuses what
interpret mode accepts (unaligned kernel slices, too much fast memory, a
program larger than the device). Nothing runs, so these tests say
nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

KERNEL = "tpu_custom_call"
V5E_HBM = 16 * 10 ** 9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (B, R): qwen3_4b's served state (4 slots x 256 tokens x d_model 2560 =
# 5120 lane rows per slot) and the toy classifiers' d=256 state (2 lane
# rows, padded to 8 sublanes)
@pytest.mark.parametrize("B,R", [(4, 5120), (4, 8)],
                         ids=["qwen3_4b", "toy"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("with_g", [False, True], ids=["no_g", "g"])
def test_rk_update_compiles_to_a_tpu_kernel(one_chip, B, R, dtype, with_g):
    from repro.kernels.hyper_step.hyper_step import LANES, rk_update_batched

    plane = _shape(one_chip, (B, R, LANES), dtype)
    row = lambda dt: _shape(one_chip, (B,), dt)

    def update(z, r, g, eps, epsp, act):
        return rk_update_batched(z, (r,), g, eps, epsp, act, (1.0,),
                                 interpret=False)

    compiled = jax.jit(update).lower(
        plane, plane, plane if with_g else None, row(jnp.float32),
        row(jnp.float32), row(jnp.int32)).compile()
    assert KERNEL in compiled.as_text()


def test_depth_field_group_fits_one_v5e(one_chip):
    """One depth-field group evaluation of qwen3_4b at published widths,
    weights passed as shapes: it compiles for the chip, reads every
    group's weights as an argument, and fits in the chip's HBM."""
    from repro.configs import get
    from repro.models.cdepth import depth_field
    from repro.models.lm import init_lm

    cfg = get("qwen3_4b")
    params = jax.tree_util.tree_map(
        lambda l: _shape(one_chip, l.shape, l.dtype),
        jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0)))
    group_bytes = sum(l.size * l.dtype.itemsize for l in
                      jax.tree_util.tree_leaves(params["groups"]))
    assert 7.0e9 < group_bytes < 7.6e9, group_bytes   # 36 x ~101 M bf16
    h = _shape(one_chip, (4, 256, cfg.d_model), jnp.bfloat16)
    s = _shape(one_chip, (), jnp.float32)
    field = depth_field(cfg)
    compiled = jax.jit(field).lower(params, s, h).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= group_bytes
    # no copy of a group's weights beyond one, and room to spare
    assert mem.temp_size_in_bytes < group_bytes / 8, mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < V5E_HBM
