"""ops/causal_conv.py (the Mamba layers' causal convolution, bias and silu as
one op with its own backward): its kernels, interpreted, against its array
form, and both against a plain float32 ``lax.conv_general_dilated``; what
``impl=None`` chooses.

Tolerances: the op computes in float32 and rounds once, so for float32
inputs all three agree to the order of their sums (2e-6 of the largest
value); for bfloat16 inputs each rounds its float32 numbers once, so any
two are at most an ulp of bfloat16 apart (2^-7 of the value).  ``dx`` comes
back in ``x``'s dtype, ``dw`` and ``db`` in float32.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepfake_detection_tpu.ops import causal_conv as CC      # noqa: E402
from tools.bench_causal_conv import replaced_expression       # noqa: E402
from deepfake_detection_tpu.ops.causal_conv import (  # noqa: E402
    causal_conv1d, causal_conv_impl)

TILE = CC._ROWS


def reference(x, w, b, activation="silu"):
    """Float32, ``highest``: a depthwise convolution padded in front."""
    k, c = w.shape
    y = lax.conv_general_dilated(
        x.astype(jnp.float32), w[:, None, :], (1,), [(k - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=c,
        precision=lax.Precision.HIGHEST) + b
    return jax.nn.silu(y) if activation == "silu" else y


def _inputs(batch, l, c, dtype, d_conv=4, seed=0):
    """(x, w, b) and a cotangent that ``x``'s dtype holds exactly (a
    bfloat16 result's cotangent is rounded to bfloat16 on its way back)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (batch, l, c), dtype),
            jax.random.normal(k[1], (d_conv, c)) * 0.5,
            jax.random.normal(k[2], (c,)) * 0.1), \
        jax.random.normal(k[3], (batch, l, c), dtype).astype(jnp.float32)


def _value_and_grads(fn, args, cot):
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot),
        argnums=(0, 1, 2))(*args)


def _close(a, b, tol):
    a, b = (np.asarray(v, np.float32) for v in (a, b))
    np.testing.assert_allclose(a, b, rtol=tol,
                               atol=tol * float(np.max(np.abs(b))))


# a row of one tile, of three (the halo in both directions), batch 2; one
# slab of 128 lanes (384 has no slab of 256) and several of 256; the cell's
# 4,352 channels
KERNEL_SHAPES = [
    pytest.param(2, TILE, 128, id="one-tile-c128-batch2"),
    pytest.param(1, 3 * TILE, 256, id="three-tiles-c256"),
    pytest.param(2, 2 * TILE, 384, id="two-tiles-c384-batch2"),
    pytest.param(1, 2 * TILE, 4352, id="two-tiles-c4352"),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("batch,l,c", KERNEL_SHAPES)
def test_kernels_equal_the_array_form_and_the_reference(batch, l, c, dtype):
    args, cot = _inputs(batch, l, c, dtype)
    (_, gk), (_, ga), (_, gr) = (
        _value_and_grads(fn, args, cot) for fn in (
            lambda *a: causal_conv1d(*a, impl="pallas"),
            lambda *a: causal_conv1d(*a, impl="xla"), reference))
    yk, ya = (causal_conv1d(*args, impl=i) for i in ("pallas", "xla"))
    out_tol = 2e-6 if dtype == jnp.float32 else 2 ** -7
    assert yk.dtype == ya.dtype == dtype and yk.shape == args[0].shape
    # the two forms: the same float32 numbers but for the order of four
    # sums, so a bfloat16 result may fall on either side of a rounding edge
    _close(yk, ya, out_tol)
    for k_, a_ in zip(gk[1:], ga[1:]):
        assert k_.dtype == jnp.float32
        _close(k_, a_, 1e-5)
    assert gk[0].dtype == dtype
    _close(gk[0], ga[0], out_tol)
    # against the plain convolution: float32 to the order of the sums, the
    # bfloat16 results to their one rounding
    _close(yk, reference(*args), out_tol)
    _close(gk[0], gr[0], 1e-5 if dtype == jnp.float32 else 2 ** -7)
    _close(gk[1], gr[1], 1e-5)
    _close(gk[2], gr[2], 1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("batch,l,c,d_conv", [
    (2, 100, 96, 4), (1, TILE + 40, 128, 4), (2, 3, 8, 4), (1, 64, 160, 2)],
    ids=["c96", "no-tile-divides-the-row", "shorter-than-the-taps",
         "two-taps"])
def test_array_form_equals_the_reference(batch, l, c, d_conv, dtype):
    args, cot = _inputs(batch, l, c, dtype, d_conv)
    (_, ga), (_, gr) = (_value_and_grads(fn, args, cot) for fn in (
        lambda *a: causal_conv1d(*a, impl="xla"), reference))
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    _close(causal_conv1d(*args, impl="xla"), reference(*args), tol)
    _close(ga[0], gr[0], tol)
    _close(ga[1], gr[1], 1e-5)
    _close(ga[2], gr[2], 1e-5)


@pytest.mark.parametrize("d_conv,bias", [(4, True), (3, False)],
                         ids=["four-taps-bias", "three-taps-no-bias"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_no_activation_is_the_convolution_and_its_bias(impl, d_conv, bias):
    """``b=None`` (models/lfm2moe.py's gated short convolution: three taps,
    no bias, no activation) is the convolution alone."""
    (x, w, b), cot = _inputs(1, TILE, 128, jnp.float32, d_conv, seed=2)
    zero = jnp.zeros_like(b)

    def op(x, w, b):
        return causal_conv1d(x, w, b if bias else None, activation=None,
                             impl=impl)

    def ref(x, w, b):
        return reference(x, w, b if bias else zero, activation=None)
    (_, g), (_, gr) = (_value_and_grads(fn, (x, w, b), cot)
                       for fn in (op, ref))
    _close(op(x, w, b), ref(x, w, b), 2e-6)
    for a, r in zip(g[:2 + bias], gr[:2 + bias]):
        _close(a, r, 1e-5)
    if not bias:
        assert not np.any(np.asarray(g[2]))


def test_the_result_looks_back_only():
    """Row ``l`` of the result depends on rows ``l - 3 .. l`` of ``x``: a
    change of row ``TILE`` (a tile's first) moves rows ``TILE .. TILE + 3``
    and nothing before them, across the tile's edge."""
    (x, w, b), _ = _inputs(1, 2 * TILE, 128, jnp.float32, seed=3)
    moved = x.at[0, TILE].add(1.0)
    for impl in ("xla", "pallas"):
        d = np.abs(np.asarray(causal_conv1d(moved, w, b, impl=impl)
                              - causal_conv1d(x, w, b, impl=impl))).max(-1)[0]
        assert np.all(d[:TILE] == 0) and np.all(d[TILE + 4:] == 0)
        assert np.all(d[TILE:TILE + 4] > 0)


def test_the_residuals_are_the_operands():
    """What the forward keeps for the backward is ``x``, ``w`` and ``b``:
    no pre-activation and no shifted copy."""
    (x, w, b), _ = _inputs(1, TILE, 128, jnp.bfloat16)
    for impl in ("xla", "pallas"):
        _, vjp = jax.vjp(lambda *a: causal_conv1d(*a, impl=impl), x, w, b)
        kept = sorted((tuple(v.shape), str(v.dtype))
                      for v in jax.tree.leaves(vjp))
        assert kept == sorted([((1, TILE, 128), "bfloat16"),
                               ((4, 128), "float32"), ((128,), "float32")])


def test_impl_none_chooses_by_backend_and_shape(monkeypatch):
    """Off a TPU every shape takes the array form; on one, the kernels
    where whole tiles hold the row (C a multiple of 128, L of the row
    tile), as both cells' shapes do."""
    assert causal_conv_impl(16384, 4352) == "xla"          # this is the CPU
    on_tpu = lambda l, c: causal_conv_impl(l, c, backend="tpu")  # noqa: E731
    assert on_tpu(16384, 4352) == "pallas"                 # granite
    assert on_tpu(16384, 5120) == "pallas"                 # phi4
    assert on_tpu(TILE, 128) == "pallas"
    assert on_tpu(TILE + 16, 128) == "xla"
    assert on_tpu(40, 160) == "xla"                        # the tiny models
    assert on_tpu(16384, 4352 + 64) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert causal_conv_impl(16384, 4352) == "pallas"


def test_impl_none_on_the_cpu_is_the_array_form():
    (x, w, b), _ = _inputs(1, TILE, 128, jnp.float32)
    text = jax.jit(causal_conv1d).lower(x, w, b).as_text()
    assert "custom_call" not in text
    np.testing.assert_array_equal(causal_conv1d(x, w, b),
                                  causal_conv1d(x, w, b, impl="xla"))


def test_the_kernels_refuse_a_shape_no_tile_holds():
    (x, w, b), _ = _inputs(1, TILE + 8, 128, jnp.float32)
    with pytest.raises(AssertionError):
        causal_conv1d(x, w, b, impl="pallas")


def assert_the_op_is_the_expression_it_replaced(loss_and_grads, params,
                                                module, monkeypatch):
    """A tiny model's loss and gradients with the op against the same model
    with the expression of before PR 31 in its place (tests/test_granite4h.py
    and tests/test_phi4flash.py).  ``loss_and_grads(dtype)`` gives the
    model's ``value_and_grad`` at that compute dtype; ``module`` is the
    model's, whose ``causal_conv1d`` is rebound.  In float32 the two differ
    by the order of four sums.  In bfloat16 the expression rounds every
    product and partial sum where the op rounds once, and a tiny model's
    gradients carry 5% of bfloat16 noise in the median leaf whichever is
    used: the loss agrees, and the op is no further from the float32
    model's gradients than the expression was."""
    def errors(g, ref):
        return sorted(
            float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
            for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref)))
    f32, bf16 = loss_and_grads(None), loss_and_grads(jnp.bfloat16)
    (l1, g1), (lb1, gb1) = f32(params), bf16(params)
    monkeypatch.setattr(module, "causal_conv1d", replaced_expression)
    (l2, g2), (lb2, gb2) = f32(params), bf16(params)
    assert abs(float(l1) - float(l2)) < 1e-6 * abs(float(l2))
    assert errors(g1, g2)[-1] < 1e-5
    assert abs(float(lb1) - float(lb2)) < 1e-4 * abs(float(lb2))
    op, was = errors(gb1, g1), errors(gb2, g1)
    assert op[len(op) // 2] < 1.1 * was[len(was) // 2] < 0.1
