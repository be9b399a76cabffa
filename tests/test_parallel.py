"""Distributed runtime tests on the 8-device CPU mesh (SURVEY.md §4)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepfake_detection_tpu.parallel import (batch_sharding, distribute_bn,
                                             fsdp_param_specs, full_attention,
                                             make_mesh, param_sharding,
                                             ring_attention,
                                             ring_self_attention, shard_batch,
                                             ulysses_attention)


class TestMesh:
    def test_default_1d(self, devices):
        mesh = make_mesh()
        assert mesh.axis_names == ("data",)
        assert mesh.shape["data"] == 8

    def test_2d_with_inference(self, devices):
        mesh = make_mesh((-1, 2), ("data", "model"))
        assert mesh.shape["data"] == 4
        assert mesh.shape["model"] == 2

    def test_bad_shape_raises(self, devices):
        with pytest.raises(AssertionError):
            make_mesh((3, 2), ("data", "model"))


class TestTrainMesh:
    def test_unified_axes_and_inference(self, devices):
        from deepfake_detection_tpu.parallel import (data_axis_name,
                                                     make_train_mesh)
        mesh = make_train_mesh()
        assert mesh.axis_names == ("batch", "model")
        assert mesh.shape["batch"] == 8 and mesh.shape["model"] == 1
        assert data_axis_name(mesh) == "batch"
        mesh2 = make_train_mesh(batch=-1, model=2)
        assert mesh2.shape["batch"] == 4 and mesh2.shape["model"] == 2

    def test_data_axis_name_legacy_and_fallback(self, devices):
        from deepfake_detection_tpu.parallel import data_axis_name
        assert data_axis_name(make_mesh()) == "data"
        assert data_axis_name(make_mesh((8,), ("replica",))) == "replica"

    def test_batch_sharding_resolves_mesh_axis(self, devices):
        from deepfake_detection_tpu.parallel import make_train_mesh
        sh = batch_sharding(make_train_mesh())
        assert sh.spec == P("batch")
        assert batch_sharding(make_mesh()).spec == P("data")


class TestTrainStateShardingTable:
    """The ISSUE 12 sharding-rule table: every TrainState leaf gets its
    NamedSharding, opt moments and EMA follow their params."""

    def _state(self, with_ema=False):
        from types import SimpleNamespace
        from deepfake_detection_tpu.models import create_model, init_model
        from deepfake_detection_tpu.optim import create_optimizer
        from deepfake_detection_tpu.train import create_train_state
        m = create_model("mnasnet_small", num_classes=2, in_chans=3)
        v = init_model(m, jax.random.PRNGKey(0), (2, 32, 32, 3),
                       training=True)
        tx = create_optimizer(SimpleNamespace(
            opt="rmsproptf", opt_eps=1e-3, momentum=0.9, weight_decay=0.0,
            lr=1e-3, decay_rate=0.9), inject=True)
        return create_train_state(v, tx, with_ema=with_ema)

    def test_default_rules_congruent_and_replicated(self, devices):
        from deepfake_detection_tpu.parallel import (make_train_mesh,
                                                     train_state_shardings)
        state = self._state(with_ema=True)
        mesh = make_train_mesh()
        sh = train_state_shardings(state, mesh)
        # congruent tree: one NamedSharding per leaf
        flat_s, tree_s = jax.tree.flatten(sh)
        flat_x, tree_x = jax.tree.flatten(state)
        assert tree_s == tree_x
        assert all(isinstance(s, NamedSharding) for s in flat_s)
        # pure DP: everything replicated
        assert all(s.spec == P() for s in flat_s)

    def test_fsdp_rule_propagates_to_moments_and_ema(self, devices):
        from deepfake_detection_tpu.parallel import (make_train_mesh,
                                                     train_state_shardings)
        state = self._state(with_ema=True)
        mesh = make_train_mesh()
        sh = train_state_shardings(state, mesh, fsdp=True)
        sharded_params = [s for s in jax.tree.leaves(sh.params)
                         if s.spec != P()]
        assert sharded_params, "no param leaf was FSDP-sharded"
        # the opt-state moments mirror the params tree → same specs
        p_specs = [s.spec for s in jax.tree.leaves(sh.params)]
        opt_named = [s.spec for s in jax.tree.leaves(sh.opt_state)
                     if s.spec != P()]
        assert opt_named, "no moment leaf followed its param's sharding"
        assert set(map(str, opt_named)) <= set(map(str, p_specs))
        ema_specs = [s.spec for s in jax.tree.leaves(sh.ema["params"])]
        assert ema_specs == p_specs
        # BN stats + step stay replicated regardless
        assert all(s.spec == P()
                   for s in jax.tree.leaves(sh.batch_stats))
        assert sh.step.spec == P()

    def test_existing_tp_placement_wins(self, devices):
        from deepfake_detection_tpu.parallel import (make_train_mesh,
                                                     train_state_shardings)
        from deepfake_detection_tpu.train.state import TrainState
        mesh = make_train_mesh(batch=-1, model=2)
        tp_sh = NamedSharding(mesh, P(None, "model"))
        params = {"w": jax.device_put(jnp.zeros((4, 8)), tp_sh),
                  "b": jnp.zeros((8,))}
        state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                           batch_stats={}, opt_state=(), ema=None)
        sh = train_state_shardings(state, mesh)
        assert sh.params["w"].spec == P(None, "model")
        assert sh.params["b"].spec == P()

    def test_place_train_state_lays_out(self, devices):
        from deepfake_detection_tpu.parallel import (make_train_mesh,
                                                     place_train_state,
                                                     train_state_shardings)
        state = self._state()
        mesh = make_train_mesh()
        sh = train_state_shardings(state, mesh, fsdp=True)
        placed = place_train_state(state, sh)
        for leaf, want in zip(jax.tree.leaves(placed),
                              jax.tree.leaves(sh)):
            assert leaf.sharding == want, (leaf.sharding, want)


class TestSharding:
    def test_batch_sharding_distributes_rows(self, devices):
        mesh = make_mesh()
        x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        arr = shard_batch(x, mesh)
        assert arr.shape == (16, 4)
        assert len(arr.addressable_shards) == 8
        assert arr.addressable_shards[0].data.shape == (2, 4)
        np.testing.assert_array_equal(np.asarray(arr), x)

    def test_fsdp_specs(self, devices):
        mesh = make_mesh()
        params = {"big": jnp.zeros((1024, 256)), "small": jnp.zeros((7,)),
                  "odd": jnp.zeros((129, 3, 3, 129))}
        specs = fsdp_param_specs(params, mesh, min_size=1024)
        assert specs["big"] == P("data", None)   # largest dim divisible by 8
        assert specs["small"] == P()             # too small
        assert specs["odd"] == P()               # nothing divisible
        shardings = param_sharding(params, mesh, fsdp=True)
        assert isinstance(shardings["big"], NamedSharding)

    def test_pjit_dp_matmul(self, devices):
        mesh = make_mesh()
        w = jnp.ones((4, 2))
        x = shard_batch(np.ones((16, 4), np.float32), mesh)

        @functools.partial(jax.jit,
                           out_shardings=NamedSharding(mesh, P()))
        def step(w, x):
            return (x @ w).sum()

        assert float(step(w, x)) == 16 * 4 * 2


class TestDistributeBn:
    def test_replicated_identity(self):
        stats = {"mean": jnp.ones(4)}
        out = distribute_bn(stats, "reduce", inside_pjit=False)
        np.testing.assert_array_equal(np.asarray(out["mean"]), 1.0)

    def test_reduce_inside_shard_map(self, devices):
        from jax import shard_map
        mesh = make_mesh()

        def f(stats):
            return distribute_bn(stats, "reduce", inside_pjit=True)

        stats = {"mean": np.arange(8, dtype=np.float32).reshape(8, 1)}
        out = shard_map(f, mesh=mesh, in_specs=({"mean": P("data", None)},),
                        out_specs={"mean": P("data", None)})(stats)
        np.testing.assert_allclose(np.asarray(out["mean"]),
                                   np.full((8, 1), 3.5))

    def test_broadcast_inside_shard_map(self, devices):
        from jax import shard_map
        mesh = make_mesh()

        def f(stats):
            return distribute_bn(stats, "broadcast", inside_pjit=True)

        stats = {"mean": np.arange(8, dtype=np.float32).reshape(8, 1)}
        out = shard_map(f, mesh=mesh, in_specs=({"mean": P("data", None)},),
                        out_specs={"mean": P("data", None)})(stats)
        np.testing.assert_allclose(np.asarray(out["mean"]),
                                   np.zeros((8, 1)))  # rank 0's value


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, devices, causal):
        mesh = make_mesh()
        b, l, h, d = 2, 32, 4, 8
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
        ref = full_attention(q, k, v, causal=causal)
        out = ring_self_attention(q, k, v, mesh, seq_axis="data",
                                  causal=causal, impl="ring")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_ulysses_matches_full_attention(self, devices):
        mesh = make_mesh()
        b, l, h, d = 2, 32, 8, 4            # heads divisible by 8
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
        ref = full_attention(q, k, v)
        out = ring_self_attention(q, k, v, mesh, seq_axis="data",
                                  impl="ulysses")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_ring_jits_under_shard_map(self, devices):
        mesh = make_mesh()
        b, l, h, d = 1, 16, 2, 4
        x = jnp.ones((b, l, h, d), jnp.float32)
        f = jax.jit(lambda q, k, v: ring_self_attention(q, k, v, mesh))
        out = f(x, x, x)
        assert out.shape == (b, l, h, d)
