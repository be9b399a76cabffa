"""ops/flash_attention.py: the window, grouped heads, a wider value head
and bfloat16 MXU operands, against dense masked attention (interpret
mode); and the old callers' results, bit for bit those of the parent
commit's kernels (tests/fixtures/flash_attention_parent.npz)."""

import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfake_detection_tpu.ops.flash_attention import flash_attention

FA = importlib.import_module("deepfake_detection_tpu.ops.flash_attention")
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "flash_attention_parent.npz")


def dense(q, k, v, window, scale):
    h, l = q.shape[2], q.shape[1]
    k = jnp.repeat(k, h // k.shape[2], axis=2)
    v = jnp.repeat(v, h // v.shape[2], axis=2)
    s = jnp.einsum("blhd,bmhd->bhlm", q, k, precision="highest") * scale
    t, m = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
    mask = m <= t
    if window:
        mask = mask & (t - m < window)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhlm,bmhd->blhd", p, v, precision="highest")


def _qkv(l, hk, hv, dv, h=4, d=64, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(l + hk), 4)
    return (jax.random.normal(ks[0], (2, l, h, d), dtype),
            jax.random.normal(ks[1], (2, l, hk, d), dtype),
            jax.random.normal(ks[2], (2, l, hv, dv), dtype),
            jax.random.normal(ks[3], (2, l, h, dv), jnp.float32))


# window smaller than, equal to and larger than a block (128); L a multiple
# of the block and not; a window longer than the sequence; grouped heads
# and a 128-wide value head beside 64-wide keys
CASES = [
    pytest.param(300, 100, 4, 4, 64, id="window<block"),
    pytest.param(300, 128, 4, 4, 64, id="window=block"),
    pytest.param(300, 200, 4, 4, 64, id="window>block"),
    pytest.param(384, 64, 4, 4, 64, id="L-multiple-of-block"),
    pytest.param(130, 512, 4, 4, 64, id="window>L"),
    pytest.param(300, 128, 2, 1, 128, id="grouped-k2-v1-dv128"),
    pytest.param(300, None, 2, 1, 128, id="grouped-causal-no-window"),
    pytest.param(260, 1, 4, 2, 64, id="window-of-one"),
]


@pytest.mark.parametrize("l,window,hk,hv,dv", CASES)
def test_window_and_groups_equal_dense_masked_attention(l, window, hk, hv,
                                                        dv):
    q, k, v, w = _qkv(l, hk, hv, dv)
    kw = dict(causal=True, window=window, scale=0.125)
    np.testing.assert_allclose(flash_attention(q, k, v, **kw),
                               dense(q, k, v, window, 0.125),
                               rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda *a: jnp.sum(flash_attention(*a, **kw) * w),
                  (0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(dense(*a, window, 0.125) * w),
                  (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_bfloat16_operands_stay_close_to_float32():
    q, k, v, w = _qkv(260, 2, 1, 128, dtype=jnp.bfloat16)
    o = flash_attention(q, k, v, causal=True, window=96, scale=0.125,
                        dot_dtype=jnp.bfloat16)
    ref = dense(*(a.astype(jnp.float32) for a in (q, k, v)), 96, 0.125)
    assert o.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - ref))) < 0.05


def test_the_window_shrinks_the_grid_not_only_the_mask():
    """Tiles a q block's window cannot touch are not on the grid."""
    assert FA._tiles_in_window(256, 256, 512, 64) == 4
    assert FA._tiles_in_window(128, 128, 100, 64) == 3
    assert FA._tiles_in_window(128, 128, 10 ** 9, 5) == 5
    first = [int(FA._first_k_tile(jnp.int32(i), 128, 128, 200))
             for i in range(5)]
    assert first == [0, 0, 0, 1, 2]


def test_window_needs_causal():
    q, k, v, _ = _qkv(130, 4, 4, 64)
    with pytest.raises(AssertionError, match="window needs causal"):
        flash_attention(q, k, v, window=8)


@pytest.mark.parametrize("name,causal", [("plain", False), ("causal", True)])
def test_old_callers_get_the_parents_results_bit_for_bit(name, causal):
    """A caller that passes none of the new arguments runs the kernels and
    grids it ran at the parent commit."""
    gold = np.load(FIXTURE)
    ks = jax.random.split(jax.random.PRNGKey(2026), 4)
    q, k, v, w = (jax.random.normal(kk, (1, 150, 2, 32), jnp.float32)
                  for kk in ks)
    out = flash_attention(q, k, v, causal=causal)
    grads = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, causal=causal) * w), (0, 1, 2))(q, k, v)
    assert np.array_equal(np.asarray(out), gold[name + "_out"])
    for n, g in zip("qkv", grads):
        assert np.array_equal(np.asarray(g), gold[f"{name}_d{n}"]), n
