"""ops/ssd.py (the chunked state-space dual scan) against the step-by-step
recurrence, its kernels against its array form, and against
ops/selective_scan.py on the case the two operators share."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfake_detection_tpu.ops.selective_scan import selective_scan
from deepfake_detection_tpu.ops.ssd import ssd_scan


def stepwise(x, dt, a, b, c, d):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + d x_t,
    one position at a time."""
    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp              # (B,H,P) (B,H) (B,N) (B,N)
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t) + d[:, None] * x_t
    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], x.dtype)
    _, y = jax.lax.scan(step, s0, tuple(v.swapaxes(0, 1)
                                        for v in (x, dt, b, c)))
    return y.swapaxes(0, 1)


def _inputs(l, batch=2, h=4, p=8, n=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (batch, l, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (batch, l, h)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (batch, l, n)),
            jax.random.normal(k[4], (batch, l, n)),
            jax.random.normal(k[5], (h,))), \
        jax.random.normal(k[6], (batch, l, h, p))


def _close(a, b, tol):
    np.testing.assert_allclose(a, b, rtol=tol,
                               atol=tol * float(jnp.max(jnp.abs(b))))


# lengths that are and are not multiples of the chunk, a chunk of one
SHAPES = [(37, 8), (64, 16), (5, 8), (130, 128), (16, 1)]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("l,chunk", SHAPES)
def test_chunked_scan_equals_the_stepwise_recurrence(l, chunk, impl):
    args, _ = _inputs(l)
    _close(ssd_scan(*args, chunk=chunk, impl=impl), stepwise(*args), 2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("l,chunk", [(37, 8), (48, 16), (9, 32)])
def test_gradients_equal_the_stepwise_recurrences(l, chunk, impl):
    args, w = _inputs(l, seed=1)
    g1 = jax.grad(lambda *v: jnp.sum(ssd_scan(*v, chunk=chunk, impl=impl)
                                     * w), argnums=range(6))(*args)
    g2 = jax.grad(lambda *v: jnp.sum(stepwise(*v) * w),
                  argnums=range(6))(*args)
    for a, b in zip(g1, g2):
        _close(a, b, 1e-4)


def test_kernels_equal_the_array_form_over_several_head_blocks():
    """16 heads are two of the kernels' head blocks; three chunks carry the
    state and its gradient across both."""
    args, w = _inputs(40, batch=1, h=16, p=4, n=8, seed=3)
    f = lambda impl: jax.value_and_grad(                      # noqa: E731
        lambda *v: jnp.sum(ssd_scan(*v, chunk=16, impl=impl) * w),
        argnums=range(6))(*args)
    (ya, ga), (yb, gb) = f("xla"), f("pallas")
    np.testing.assert_allclose(ya, yb, rtol=1e-5)
    for a, b in zip(ga, gb):
        _close(a, b, 2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_bfloat16_in_and_out_float32_inside(impl):
    """bf16 operands: y comes back in bf16 and, over 2,048 positions of slow
    decay, stays within operand rounding of the float32 recurrence: a state
    or a decay sum kept in bf16 would drift by far more."""
    (x, dt, a, b, c, d), w = _inputs(2048, batch=1, seed=2)
    dt, a = dt * 0.01, a * 0.1
    lo = lambda v: v.astype(jnp.bfloat16)                     # noqa: E731
    y = ssd_scan(lo(x), dt, a, lo(b), lo(c), d, chunk=64, impl=impl)
    assert y.dtype == jnp.bfloat16
    f32 = lambda v: lo(v).astype(jnp.float32)                 # noqa: E731
    ref = stepwise(f32(x), dt, a, f32(b), f32(c), d)
    err = jnp.linalg.norm(y.astype(jnp.float32) - ref) / jnp.linalg.norm(ref)
    assert float(err) < 1e-2, float(err)
    g = jax.grad(lambda v: jnp.sum(ssd_scan(
        v, dt, a, lo(b), lo(c), d, chunk=64, impl=impl).astype(jnp.float32)
        * w))(lo(x))
    assert g.dtype == jnp.bfloat16
    gr = jax.grad(lambda v: jnp.sum(stepwise(
        v, dt, a, f32(b), f32(c), d) * w))(f32(x))
    err = jnp.linalg.norm(g.astype(jnp.float32) - gr) / jnp.linalg.norm(gr)
    assert float(err) < 1e-2, float(err)


def test_selective_scan_with_a_broadcast_over_heads_gives_the_same_y():
    """Mamba-2's recurrence is Mamba-1's with A[c, n] = a[head of c] and
    delta[c] = dt[head of c]: the two operators check each other."""
    (x, dt, a, b, c, d), w = _inputs(50, h=3, p=8, n=4, seed=4)
    batch, l, h, p = x.shape
    wide = lambda v: jnp.repeat(v, p, axis=-1)                # noqa: E731
    one = lambda *v: jnp.sum(selective_scan(                  # noqa: E731
        v[0].reshape(batch, l, h * p), wide(v[1]),
        jnp.broadcast_to(wide(v[2])[:, None], (h * p, 4)), v[3], v[4],
        wide(v[5]), chunk=16, impl="lax").reshape(x.shape) * w)
    two = lambda *v: jnp.sum(ssd_scan(*v, chunk=16, impl="xla") * w)  # noqa
    args = (x, dt, a, b, c, d)
    np.testing.assert_allclose(one(*args), two(*args), rtol=1e-5)
    for g1, g2 in zip(jax.grad(one, range(6))(*args),
                      jax.grad(two, range(6))(*args)):
        _close(g1, g2, 1e-4)
