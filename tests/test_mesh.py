"""launch/mesh.py policy layer — the request-facing checks that run
BEFORE any device mesh is touched, so they are testable on the
single-device CPU test process (the full sharded-solve semantics run in
the forced-4-device subprocess in test_runtime_eps.py).

Pinned decision: a batch that does not divide the mesh's data-axis size
is a CLEAR ERROR naming the remedy, not silent pad-and-trim — padding
would fabricate requests whose NFE/latency accounting the serving layer
then misreports."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FixedGrid, Integrator, get_tableau
from repro.launch.mesh import batch_axes, sharded_solve


class _StubMesh:
    """Shape/axis metadata double for the pre-dispatch policy checks
    (sharded_solve reads nothing else before raising)."""

    def __init__(self, n_data=3):
        self.shape = {"data": n_data, "model": 2}
        self.axis_names = ("data", "model")


def _field(s, z):
    return -z


def test_sharded_solve_rejects_indivisible_batch():
    """PINNED: batch % data-axis != 0 raises the clear error (with the
    re-bucket remedy) before any shard_map/device work happens."""
    integ = Integrator(get_tableau("euler"))
    z0 = jnp.ones((8, 4))  # 8 % 3 != 0
    with pytest.raises(ValueError, match="does not divide"):
        sharded_solve(integ, _field, z0, FixedGrid.over(0.0, 1.0, 2),
                      mesh=_StubMesh(n_data=3))


def test_sharded_solve_rejects_indivisible_pytree_batch():
    """The divisibility check keys on the leading axis of the FIRST leaf
    — a pytree state hits the same clear error."""
    integ = Integrator(get_tableau("euler"))
    z0 = (jnp.ones((5, 3)), jnp.ones((5, 2)))
    with pytest.raises(ValueError, match="does not divide"):
        sharded_solve(integ, lambda s, z: z, z0,
                      FixedGrid.over(0.0, 1.0, 2), mesh=_StubMesh(n_data=2))


def test_sharded_solve_rejects_bad_eps_rank():
    """grid.eps beyond (B,) is a policy error too, caught pre-dispatch."""
    integ = Integrator(get_tableau("euler"))
    z0 = jnp.ones((6, 4))
    bad = FixedGrid(0.0, jnp.ones((6, 2)), 2)  # eps ndim == 2
    with pytest.raises(ValueError, match="scalar or"):
        sharded_solve(integ, _field, z0, bad, mesh=_StubMesh(n_data=3))


def test_solve_segment_rejects_indivisible_slot_count():
    """PINNED (mirroring the batch-axis decision): a slot pool the mesh
    axis cannot split row-wise is a CLEAR ERROR naming the remedy, raised
    before any shard_map/device work happens."""
    from repro.core import make_segment_carry

    integ = Integrator(get_tableau("euler"))
    carry = make_segment_carry(jnp.ones((8, 4)), jnp.full((8,), 2),
                               (0.0, 1.0))  # 8 slots % 3 != 0
    with pytest.raises(ValueError, match="does not divide"):
        integ.solve_segment(_field, carry, 2, mesh=_StubMesh(n_data=3))


def test_sharded_segment_rejects_indivisible_slot_count():
    """The conditioning-threading helper hits the same pre-dispatch check."""
    from repro.core import make_segment_carry
    from repro.launch.mesh import sharded_segment

    integ = Integrator(get_tableau("euler"))
    carry = make_segment_carry(jnp.ones((5, 4)), jnp.full((5,), 2),
                               (0.0, 1.0))
    with pytest.raises(ValueError, match="does not divide"):
        sharded_segment(integ, lambda p, x: _field, None,
                        jnp.ones((5, 4)), carry, 2,
                        mesh=_StubMesh(n_data=2))


def test_inflight_scheduler_rejects_indivisible_slots():
    """InflightScheduler(mesh=) validates the GLOBAL pool width against
    the slot axis at construction — before any queue state exists."""
    from repro.launch.engine import DepthModel, EngineConfig
    from repro.launch.scheduler import InflightScheduler

    model = DepthModel(embed=lambda p, x: x, field_of=lambda p, x: _field,
                       readout=lambda p, x, zT: zT,
                       integ=Integrator(get_tableau("euler")))
    with pytest.raises(ValueError, match="does not divide"):
        InflightScheduler(model, EngineConfig(), slots=5, seg=2,
                          mesh=_StubMesh(n_data=3))


def test_make_serving_mesh_rejects_oversubscription():
    """--mesh N beyond the visible device count is a clear error naming
    the XLA_FLAGS remedy, not an opaque make_mesh failure."""
    import jax

    from repro.launch.mesh import make_serving_mesh

    with pytest.raises(ValueError, match="XLA_FLAGS"):
        make_serving_mesh(jax.device_count() + 1)
    with pytest.raises(ValueError, match=">= 1"):
        make_serving_mesh(0)


def test_batch_axes_policy():
    assert batch_axes(_StubMesh()) == ("data",)

    class _PodMesh(_StubMesh):
        def __init__(self):
            super().__init__()
            self.axis_names = ("pod", "data", "model")

    assert batch_axes(_PodMesh()) == ("pod", "data")
