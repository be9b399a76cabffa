"""Pallas flash-attention parity vs dense reference (CPU interpreter).

On CPU these run the actual kernel bodies under the Pallas interpreter, so
block streaming, masking, and the custom-VJP backward are all exercised —
only the Mosaic codegen itself is TPU-only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfake_detection_tpu.ops.flash_attention import flash_attention
from deepfake_detection_tpu.parallel.ring_attention import full_attention


def _qkv(b, l, h, d, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, l, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("l,d,causal", [
    (64, 32, False),       # single block, sub-lane head dim (pads to 128)
    (200, 64, False),      # ragged L: pad + key masking (ViT-224 is L=197)
    (256, 64, True),       # multi-block causal
    (320, 48, True),       # ragged causal + ragged D
])
def test_forward_matches_dense(l, d, causal):
    q, k, v = _qkv(2, l, 3, d)
    out = flash_attention(q, k, v, causal=causal)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_forward_small_blocks():
    # force multi-block streaming even at tiny L by shrinking the tiles
    q, k, v = _qkv(1, 384, 2, 64, seed=3)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_dense(causal):
    q, k, v = _qkv(2, 160, 2, 32, seed=1)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_bf16_inputs():
    q, k, v = _qkv(1, 128, 2, 64, seed=2, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_jit_and_vit_integration():
    from deepfake_detection_tpu.models import create_model, init_model
    model = create_model("vit_tiny_patch16_224", num_classes=2,
                         attn_impl="flash")
    variables = init_model(model, jax.random.PRNGKey(0), (1, 64, 64, 3))
    x = jnp.zeros((1, 64, 64, 3))
    logits = jax.jit(
        lambda v, x: model.apply(v, x, training=False))(variables, x)
    assert logits.shape == (1, 2)
    ref_model = create_model("vit_tiny_patch16_224", num_classes=2)
    ref = ref_model.apply(variables, x, training=False)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# ---- remat keeps what the forward made ---------------------------------------

def _kernels(jaxpr, names=None):
    """The kernels' names of every ``pallas_call`` in ``jaxpr`` and the
    jaxprs nested in it (remat, custom VJP, jit), in order."""
    from jax.extend import core as jcore
    names = [] if names is None else names
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["jaxpr"].debug_info.func_name)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                if isinstance(sub, jcore.ClosedJaxpr):
                    _kernels(sub.jaxpr, names)
                elif isinstance(sub, jcore.Jaxpr):
                    _kernels(sub, names)
    return names


def _attention_block(heads, kv_heads, v_heads, d, dv, window):
    """A residual block around one flash attention call, with projections
    before and after it, as the sequence models' layers have."""
    import flax.linen as nn

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x, training=False):
            b, l, _ = x.shape
            q = nn.Dense(heads * d)(x).reshape(b, l, heads, d)
            k = nn.Dense(kv_heads * d)(x).reshape(b, l, kv_heads, d)
            v = nn.Dense(v_heads * dv)(x).reshape(b, l, v_heads, dv)
            o = flash_attention(q, k, v, causal=True, window=window)
            return x + nn.Dense(x.shape[-1])(o.reshape(b, l, heads * dv))
    return Block


ATTENTION_CASES = {
    "causal": (2, 2, 2, 64, 64, None),
    "window": (2, 2, 2, 64, 64, 48),
    "grouped-64": (4, 2, 1, 64, 64, None),
    "grouped-256": (4, 1, 2, 256, 256, None),
}


@pytest.mark.parametrize("case", sorted(ATTENTION_CASES))
@pytest.mark.parametrize("policy", ["full", "dots"])
def test_remat_runs_the_forward_kernel_once(policy, case):
    """Under ``maybe_remat``'s ``full`` and ``dots`` the gradient of a block
    that calls the op runs the forward kernel once: the policy keeps the
    output and row statistics the op names (``FLASH_RESIDUALS``), so the
    rematerialised block does not launch it again (it did before they
    were saved: two forwards and the backward).  The gradients are those
    of the block with nothing rematerialised."""
    from deepfake_detection_tpu.models.helpers import maybe_remat
    block = _attention_block(*ATTENTION_CASES[case])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 160, 16))
    params = block().init(jax.random.PRNGKey(1), x, False)

    def grad(policy):
        m = maybe_remat(block, policy)()
        return jax.grad(lambda p: (m.apply(p, x, False) ** 2).sum())

    kernels = _kernels(jax.make_jaxpr(grad(policy))(params).jaxpr)
    assert kernels.count("_fwd_kernel") == 1, kernels
    assert kernels.count("_bwd_fused_kernel") == 1, kernels
    for a, b in zip(jax.tree.leaves(grad("none")(params)),
                    jax.tree.leaves(grad(policy)(params))):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5)


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_remat_of_a_block_without_attention_lowers_as_before(policy):
    """A block that calls no flash op has none of its names: its gradient
    under ``maybe_remat`` lowers to the text of the plain ``nn.remat`` the
    policy was before (nothing saved for ``full``, the matmul/conv outputs
    for ``dots``): the EfficientNet and image models' steps are the
    parent's."""
    import flax.linen as nn
    from deepfake_detection_tpu.models.efficientnet_blocks import \
        InvertedResidual
    from deepfake_detection_tpu.models.helpers import maybe_remat
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 8))
    kw = dict(out_chs=8, exp_ratio=4.0, se_ratio=0.25, act="swish")
    variables = InvertedResidual(**kw).init(jax.random.PRNGKey(1), x, True)
    old = None if policy == "full" else jax.checkpoint_policies.checkpoint_dots

    def text(cls):
        m = cls(**kw)

        def loss(p):
            y, _ = m.apply({"params": p,
                            "batch_stats": variables["batch_stats"]}, x, True,
                           mutable=["batch_stats"])
            return (y ** 2).sum()
        return jax.jit(jax.grad(loss)).lower(variables["params"]).as_text()

    assert text(maybe_remat(InvertedResidual, policy)) == text(
        nn.remat(InvertedResidual, policy=old, static_argnums=(2,)))
