"""ViT: param parity, forward, ring-attention sequence parallelism, e2e."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfake_detection_tpu.models import create_model, init_model


def test_vit_base_param_count():
    # canonical timm vit_base_patch16_224 @1000 classes
    m = create_model("vit_base_patch16_224", num_classes=1000)
    shapes = jax.eval_shape(
        lambda r: m.init(r, jnp.zeros((1, 224, 224, 3)), training=False),
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)})
    n = sum(int(jnp.prod(jnp.asarray(x.shape)))
            for x in jax.tree.leaves(shapes["params"]))
    assert n == 86_567_656


def test_vit_forward_and_pools():
    m = create_model("vit_tiny_patch16_224", num_classes=4)
    v = init_model(m, jax.random.PRNGKey(0), (2, 64, 64, 3))
    out = m.apply(v, jnp.zeros((2, 64, 64, 3)), training=False)
    assert out.shape == (2, 4)
    m2 = create_model("vit_tiny_patch16_224", num_classes=4,
                      class_token=False, global_pool="avg")
    v2 = init_model(m2, jax.random.PRNGKey(0), (2, 64, 64, 3))
    assert "cls_token" not in v2["params"]
    assert m2.apply(v2, jnp.zeros((2, 64, 64, 3)),
                    training=False).shape == (2, 4)


def test_vit_12chan_flagship_input():
    """The deepfake 12-channel frame stack works through the patch embed."""
    m = create_model("vit_tiny_patch16_224", num_classes=2, in_chans=12)
    v = init_model(m, jax.random.PRNGKey(0), (1, 64, 64, 12))
    out = m.apply(v, jnp.zeros((1, 64, 64, 12)), training=False)
    assert out.shape == (1, 2)


class TestSequenceParallel:
    def _models(self, devices, impl):
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(devices), ("data",))
        common = dict(num_classes=2, class_token=False, global_pool="avg")
        m_full = create_model("vit_tiny_patch16_224", **common)
        m_sp = create_model("vit_tiny_patch16_224", **common,
                            attn_impl=impl, sp_mesh=mesh)
        return m_full, m_sp

    @pytest.mark.parametrize("impl", [
        "ring",
        # tier-1 budget: ring_flash is env-broken on this jaxlib
        # (PartitionId, pre-existing) and burns ~5 s failing; it stays
        # in the slow tier with the other ring_flash pins
        pytest.param("ring_flash", marks=pytest.mark.slow),
        "ulysses"])
    def test_sp_attention_matches_full(self, devices, impl):
        """128 tokens sharded 8-ways through the SP kernels must match the
        dense forward (the ViT families exist to stress the attention path)."""
        m_full, m_sp = self._models(devices, impl)
        # 128×128/16 → 64 tokens per side isn't enough for 8-way ulysses
        # heads split (3 heads) — ring shards the SEQUENCE so 64 works; for
        # ulysses heads must divide axis, so skip when they don't
        if impl == "ulysses" and 3 % len(devices) != 0:
            pytest.skip("ulysses needs heads % axis == 0 (3 heads, 8 dev)")
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 128, 3))
        v = init_model(m_full, jax.random.PRNGKey(0), (2, 128, 128, 3))
        out_full = m_full.apply(v, x, training=False)
        out_sp = jax.jit(lambda v, x: m_sp.apply(v, x, training=False))(v, x)
        np.testing.assert_allclose(np.asarray(out_full),
                                   np.asarray(out_sp), atol=2e-5)

    @pytest.mark.slow   # tier-1 budget: full SP train-step grads vs the
    # dense path (~11 s); SP forward parity (ring/ulysses above) stays
    # fast and train-step grads ride test_train's unified-step coverage
    def test_sp_train_step_grads(self, devices):
        """One jitted train step with the token axis ring-sharded: grads
        flow and match the dense path."""
        m_full, m_ring = self._models(devices, "ring")
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 128, 3))
        y = jnp.array([0, 1])
        v = init_model(m_full, jax.random.PRNGKey(0), (2, 128, 128, 3))

        def loss_fn(model):
            def inner(params):
                logits = model.apply({"params": params}, x, training=False)
                lp = jax.nn.log_softmax(logits)
                return -jnp.mean(jnp.take_along_axis(lp, y[:, None], 1))
            return inner

        g_full = jax.grad(loss_fn(m_full))(v["params"])
        g_ring = jax.jit(jax.grad(loss_fn(m_ring)))(v["params"])
        flat_f = jax.tree.leaves(g_full)
        flat_r = jax.tree.leaves(g_ring)
        assert all(np.allclose(a, b, atol=5e-5)
                   for a, b in zip(flat_f, flat_r))


@pytest.mark.slow
def test_vit_synthetic_e2e_train(tmp_path, devices):
    from deepfake_detection_tpu.runners.train import launch_main
    out = launch_main([
        "--dataset", "synthetic", "--model", "vit_tiny_patch16_224",
        "--model-version", "", "--input-size-v2", "3,32,32",
        "--batch-size", "1", "--epochs", "1", "--opt", "adamw",
        "--lr", "1e-3", "--sched", "step", "--log-interval", "4",
        "--workers", "1", "--compute-dtype", "float32",
        "--output", str(tmp_path / "out")])
    assert out["best_metric"] is not None


@pytest.mark.parametrize("policy", [
    pytest.param("full", marks=pytest.mark.slow),   # tier-1 budget
    "dots"])
def test_vit_remat_matches_baseline(policy):
    """remat changes the backward schedule, not the math."""
    base = create_model("vit_tiny_patch16_224", num_classes=2)
    rem = create_model("vit_tiny_patch16_224", num_classes=2,
                       remat_policy=policy)
    v = init_model(base, jax.random.PRNGKey(0), (1, 64, 64, 3))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64, 3))

    def loss(model):
        return lambda p: model.apply({"params": p}, x).sum()

    np.testing.assert_allclose(
        np.asarray(base.apply(v, x)), np.asarray(rem.apply(v, x)), atol=5e-6)
    g0 = jax.grad(loss(base))(v["params"])
    g1 = jax.jit(jax.grad(loss(rem)))(v["params"])
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
