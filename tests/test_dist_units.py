"""Unit tests for the repro.dist subsystem (no multi-device mesh needed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import plan_buckets
from repro.dist.elastic import surviving_mesh
from repro.dist.policy import constrain, current_policy, sharding_policy
from repro.dist.sharding import batch_spec_axes, data_axes
from repro.launch.mesh import make_host_mesh


# --------------------------------------------------------------------------- #
# bucket planning (Alg. 2 SJF at bucket granularity)
# --------------------------------------------------------------------------- #
class TestPlanBuckets:
    def test_packs_within_budget(self):
        buckets = plan_buckets([100, 100, 100, 100], 250)
        assert [b.indices for b in buckets] == [(0, 1), (2, 3)]
        assert all(b.nbytes <= 250 for b in buckets)

    def test_oversized_leaf_gets_own_bucket(self):
        buckets = plan_buckets([10, 999, 10], 100, shortest_first=False)
        assert [b.indices for b in buckets] == [(0,), (1,), (2,)]

    def test_shortest_first_orders_by_bytes(self):
        buckets = plan_buckets([900, 50, 400], 1000, shortest_first=True)
        sizes = [b.nbytes for b in buckets]
        assert sizes == sorted(sizes)
        # greedy tree-order packing gives (900+50), (400); SJF issues the
        # 400-byte bucket first
        assert buckets[0].indices == (2,)

    def test_fifo_keeps_tree_order(self):
        buckets = plan_buckets([900, 50, 400], 600, shortest_first=False)
        assert [b.indices for b in buckets] == [(0,), (1, 2)]

    def test_every_index_exactly_once(self):
        sizes = [3, 1000, 17, 256, 256, 9]
        buckets = plan_buckets(sizes, 300)
        seen = sorted(i for b in buckets for i in b.indices)
        assert seen == list(range(len(sizes)))

    def test_rejects_nonpositive_budget(self):
        with pytest.raises(ValueError):
            plan_buckets([1, 2], 0)


# --------------------------------------------------------------------------- #
# activation policy context
# --------------------------------------------------------------------------- #
class TestPolicy:
    def test_constrain_is_identity_without_policy(self):
        assert current_policy() is None
        x = jnp.ones((4, 8))
        assert constrain(x, "residual") is x

    def test_policy_binds_and_unbinds(self):
        mesh = make_host_mesh()
        with sharding_policy(mesh, {"residual": P(None, "model", None)}):
            assert current_policy() is not None
            y = constrain(jnp.ones((2, 4, 8)), "residual")
            assert y.shape == (2, 4, 8)
            # unknown names pass through untouched
            z = jnp.ones((3,))
            assert constrain(z, "nonexistent") is z
        assert current_policy() is None

    def test_non_dividing_axis_is_dropped(self):
        mesh = make_host_mesh()  # model axis exists, size = n_local_devices
        with sharding_policy(mesh, {"residual": P("model")}):
            x = jnp.ones((7,))  # 7 is coprime with any pow2 device count
            y = constrain(x, "residual")
            np.testing.assert_array_equal(np.asarray(y), np.ones(7))

    def test_constraint_applies_under_jit(self):
        mesh = make_host_mesh()
        act = {"logits": P(None, "model")}

        @jax.jit
        def f(x):
            with sharding_policy(mesh, act):
                return constrain(x, "logits") * 2
        out = f(jnp.ones((2, 8)))
        np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((2, 8)))


# --------------------------------------------------------------------------- #
# mesh helpers
# --------------------------------------------------------------------------- #
class TestMeshHelpers:
    def test_data_axes_without_pod(self):
        assert data_axes(make_host_mesh()) == ("data",)

    def test_batch_spec_axes_divisible(self):
        mesh = make_host_mesh()
        assert batch_spec_axes(mesh, 16) == ("data",)

    def test_surviving_mesh_preserves_model_axis(self):
        devs = jax.devices()
        mesh = surviving_mesh(devs, data=len(devs), model=1)
        assert mesh.shape["model"] == 1
        assert mesh.shape["data"] == len(devs)

    def test_surviving_mesh_rejects_empty(self):
        with pytest.raises(ValueError):
            surviving_mesh([], data=1, model=1)

    def test_compat_shard_map_psum(self):
        mesh = make_host_mesh(data=1)

        def body(x):
            return jax.lax.psum(x, "model")

        f = jax.shard_map(body, mesh=mesh, in_specs=P("model"),
                          out_specs=P("model"),
                          axis_names={"data", "model"}, check_vma=False)
        n = len(jax.devices())
        out = f(jnp.ones((n,)))
        np.testing.assert_array_equal(np.asarray(out), np.full((n,), n))
