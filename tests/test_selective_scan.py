"""ops/selective_scan.py against the step-by-step recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfake_detection_tpu.ops.selective_scan import selective_scan


def stepwise(u, delta, A, B, C, D):
    """s_t = exp(delta_t A) s_{t-1} + delta_t B_t u_t; y_t = C_t s_t + D u_t,
    one position at a time."""
    def step(s, x):
        u_t, d_t, b_t, c_t = x
        s = jnp.exp(d_t[:, :, None] * A) * s \
            + (d_t * u_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], -1) + D * u_t
    s0 = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]), u.dtype)
    _, y = jax.lax.scan(step, s0, tuple(x.swapaxes(0, 1)
                                        for x in (u, delta, B, C)))
    return y.swapaxes(0, 1)


def _inputs(l, b=2, d=24, n=4, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (b, l, d)),
            jax.nn.softplus(jax.random.normal(k[1], (b, l, d))),
            -jnp.exp(jax.random.normal(k[2], (d, n))),
            jax.random.normal(k[3], (b, l, n)),
            jax.random.normal(k[4], (b, l, n)),
            jax.random.normal(k[5], (d,))), jax.random.normal(k[6], (b, l, d))


@pytest.mark.parametrize("l,chunk", [(37, 8), (64, 16), (5, 8), (130, 128),
                                     (16, 1)])
def test_chunked_scan_equals_the_stepwise_recurrence(l, chunk):
    args, _ = _inputs(l)
    np.testing.assert_allclose(selective_scan(*args, chunk=chunk),
                               stepwise(*args), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("l,chunk", [(37, 8), (48, 16), (9, 32)])
def test_gradients_equal_the_stepwise_recurrences(l, chunk):
    args, w = _inputs(l, seed=1)
    g1 = jax.grad(lambda *a: jnp.sum(selective_scan(*a, chunk=chunk) * w),
                  argnums=range(6))(*args)
    g2 = jax.grad(lambda *a: jnp.sum(stepwise(*a) * w),
                  argnums=range(6))(*args)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-4 * float(jnp.max(jnp.abs(b))))


def test_bfloat16_in_and_out_float32_inside():
    args, _ = _inputs(40, seed=2)
    u, delta, A, B, C, D = args
    y = selective_scan(u.astype(jnp.bfloat16), delta, A,
                       B.astype(jnp.bfloat16), C.astype(jnp.bfloat16), D,
                       chunk=16)
    assert y.dtype == jnp.bfloat16
    ref = stepwise(*args)
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - ref))) < \
        0.05 * float(jnp.max(jnp.abs(ref)))


def test_only_chunk_starts_are_kept_for_the_backward_pass():
    """The residuals hold L / chunk states, never L of them."""
    args, _ = _inputs(64, b=1)
    from deepfake_detection_tpu.ops import selective_scan as S
    f32 = [a.astype(jnp.float32) for a in args]
    _, res = S._scan_fwd(*f32, 16, False, False)
    assert res[-1].shape == (4, 1, 4, 24)


# ---------------------------------------------------------------------------
# the TPU kernels, interpreted: against the lax form and the recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("l,chunk,b", [(40, 16, 2), (21, 8, 1), (16, 64, 1)])
def test_kernels_equal_the_stepwise_recurrence_and_the_lax_form(l, chunk, b):
    """2048 channels (two channel blocks), 16 states, L not a multiple of
    the chunk, a chunk longer than L, two rows."""
    args, w = _inputs(l, b=b, d=2048, n=16, seed=3)
    f = lambda impl: lambda *a: jnp.sum(selective_scan(      # noqa: E731
        *a, chunk=chunk, impl=impl, interpret=True) * w)
    y = selective_scan(*args, chunk=chunk, impl="pallas", interpret=True)
    np.testing.assert_allclose(y, stepwise(*args), rtol=2e-5, atol=2e-5)
    gk = jax.grad(f("pallas"), argnums=range(6))(*args)
    gl = jax.grad(f("lax"), argnums=range(6))(*args)
    gs = jax.grad(lambda *a: jnp.sum(stepwise(*a) * w),
                  argnums=range(6))(*args)
    for k, l_, s_ in zip(gk, gl, gs):
        scale = float(jnp.max(jnp.abs(s_)))
        np.testing.assert_allclose(k, s_, rtol=1e-4, atol=1e-4 * scale)
        np.testing.assert_allclose(k, l_, rtol=1e-4, atol=1e-4 * scale)


def test_kernels_keep_one_state_a_chunk_and_take_whole_channel_blocks():
    args, _ = _inputs(64, b=1, d=1024, n=16)
    from deepfake_detection_tpu.ops import selective_scan as S
    f32 = [a.astype(jnp.float32) for a in args]
    _, res = S._scan_fwd(*f32, 16, True, True)
    assert res[-1].shape == (1, 4, 16, 8, 128)      # (B, chunks, N, tiles)
    with pytest.raises(AssertionError, match="1024"):
        selective_scan(*_inputs(8, d=24)[0], impl="pallas")


def test_off_the_chip_the_default_is_the_lax_form():
    args, _ = _inputs(8, d=1024, n=16)
    jaxpr = str(jax.make_jaxpr(lambda *a: selective_scan(*a))(*args))
    assert "pallas_call" not in jaxpr
