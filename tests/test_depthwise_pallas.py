"""Fused Pallas depthwise kernel + s2d stem: parity against the XLA lowering.

Interpret-mode (CPU) checks of ops/depthwise_pallas.py — forward ≤2 ulp
against the XLA ``dw-conv → affine → act`` composition across kernel sizes
{3,5}, strides {1,2}, the reference's static-symmetric ``''`` padding
(Conv2dSame analog), TF ``'same'`` and explicit ints, in f32 and bf16; the
custom VJP (dx/dw Pallas kernels, dscale/dbias XLA reductions) at
reassociation tolerance.  Model-level: routing ``fused_depthwise='pallas'``
through DepthwiseSeparableConv/InvertedResidual must keep the parameter
tree IDENTICAL and outputs equivalent in eval and train (BN stats
included); ``stem_s2d`` must be a pure weight re-scatter — the golden-
params equivalence tests apply one shared variable tree to every variant.

On a real TPU backend the same tests compile the kernels instead of
interpreting them (``interpret=None`` auto-detects), which is the
measurement-day regression net.
"""

import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deepfake_detection_tpu.models import create_model, init_model
from deepfake_detection_tpu.models.efficientnet_blocks import (
    fused_dw_eligible)
from deepfake_detection_tpu.ops.conv import (dw_grad_census, dw_grad_impl,
                                             dw_grad_scope, resolve_padding,
                                             space_to_depth,
                                             space_to_depth_stem_kernel)
from deepfake_detection_tpu.ops.depthwise_pallas import (FUSED_DW_ACTS,
                                                         fused_depthwise)

pytestmark = [pytest.mark.smoke, pytest.mark.pallas]

_ACTS = {"none": lambda u: u, "relu": lambda u: jnp.maximum(u, 0.0),
         "silu": jax.nn.silu}


def _resolve(pad, k, stride, h, w):
    p = resolve_padding(pad, (k, k), 1, stride)
    if p == "SAME":
        def _same(n):
            need = max((-(-n // stride) - 1) * stride + k - n, 0)
            return (need // 2, need - need // 2)
        return [_same(h), _same(w)]
    if p == "VALID":
        return [(0, 0), (0, 0)]
    return [tuple(int(q) for q in pr) for pr in p]


def _xla_ref(x, w, scale, bias, stride, pad, act, with_chain=False):
    """The stage the kernel fuses, as stock XLA ops in f32.

    ``with_chain`` additionally returns the chain's ℓ1 accumulation mass
    ``Σ|x·w|·|scale| + |bias|`` — the magnitude every rounding in either
    implementation is taken against (see :func:`_assert_ulp`)."""
    k, c = w.shape[0], w.shape[-1]
    padv = _resolve(pad, k, stride, x.shape[1], x.shape[2])
    dn = ("NHWC", "HWIO", "NHWC")
    z = lax.conv_general_dilated(
        x.astype(jnp.float32), w.reshape(k, k, 1, c).astype(jnp.float32),
        (stride, stride), padv, feature_group_count=c, dimension_numbers=dn)
    u = z * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    y = _ACTS[act](u).astype(x.dtype)
    if with_chain:
        l1 = lax.conv_general_dilated(
            jnp.abs(x.astype(jnp.float32)),
            jnp.abs(w.reshape(k, k, 1, c).astype(jnp.float32)),
            (stride, stride), padv, feature_group_count=c,
            dimension_numbers=dn)
        chain = l1 * jnp.abs(scale.astype(jnp.float32)) \
            + jnp.abs(bias.astype(jnp.float32))
        return y, chain
    return y


def _assert_ulp(got, ref, chain, n_round, ulps=2):
    """|got-ref| ≤ ulps · ulp(n_round-step accumulation) elementwise.

    One "ulp" of an accumulation of ``n_round`` roundings is the standard
    Higham γ_n forward-error unit ``(n_round/2) · spacing(ℓ1 mass)``: each
    implementation carries at most n_round roundings of at most ½
    spacing(chain) each (XLA may FMA-contract some MACs, the Pallas
    interpreter may not, and tap order is unspecified), so two CORRECT
    implementations differ by at most 2 such units.  Measuring against the
    ℓ1 mass rather than the output is what makes the bound meaningful: the
    affine epilogue can cancel |y| arbitrarily far below the accumulator
    magnitude, where an output-relative bound would reject any legal
    reassociation (and pass only bit-identity, which FMA contraction
    already breaks between two XLA lowerings of the SAME expression)."""
    g32 = np.asarray(got, np.float32)
    r32 = np.asarray(ref, np.float32)
    mag = np.maximum(np.abs(r32), np.asarray(chain, np.float32))
    if got.dtype == jnp.bfloat16:
        spac = np.maximum(mag, 2.0 ** -126) * 2.0 ** -8
    else:
        spac = np.spacing(np.maximum(mag, np.float32(1e-30))
                          .astype(np.float32))
    unit = (n_round / 2.0) * spac
    bad = np.abs(g32 - r32) > ulps * unit
    assert not bad.any(), (
        f"{bad.sum()} elems exceed {ulps} accumulation-ulp "
        f"(n_round={n_round}); worst {np.abs(g32 - r32).max():.3e}")


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("pad", ["", "same"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_parity(k, stride, pad, dtype):
    dt = getattr(jnp, dtype)
    rng = np.random.default_rng(k * 10 + stride)
    x = jnp.asarray(rng.standard_normal((2, 13, 11, 24)), dt)
    w = jnp.asarray(rng.standard_normal((k, k, 24)) * 0.2, jnp.float32)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, 24), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.2, 0.2, 24), jnp.float32)
    y = fused_depthwise(x, w, scale, bias, stride=stride, padding=pad,
                        act="silu")
    ref, chain = _xla_ref(x, w, scale, bias, stride, pad, "silu",
                          with_chain=True)
    assert y.shape == ref.shape and y.dtype == ref.dtype
    _assert_ulp(y, ref, chain, n_round=k * k + 2)


@pytest.mark.parametrize("k,stride", [(3, 1), (5, 2)])
def test_forward_accuracy_vs_f64_truth(k, stride):
    """The fused kernel must be AS ACCURATE as the XLA lowering, not just
    close to it: both are compared against the float64 ground truth and the
    kernel's worst error (in spacing(chain) units) may not exceed the XLA
    conv's own worst error by more than 1 — i.e. the fusion does not trade
    numerics for speed."""
    rng = np.random.default_rng(k * 10 + stride)
    xn = rng.standard_normal((2, 13, 11, 24)).astype(np.float32)
    wn = (rng.standard_normal((k, k, 24)) * 0.2).astype(np.float32)
    sn = rng.uniform(0.5, 1.5, 24).astype(np.float32)
    bn = rng.uniform(-0.2, 0.2, 24).astype(np.float32)
    x, w = jnp.asarray(xn), jnp.asarray(wn)
    scale, bias = jnp.asarray(sn), jnp.asarray(bn)

    y = fused_depthwise(x, w, scale, bias, stride=stride, padding="",
                        act="silu")
    ref, chain = _xla_ref(x, w, scale, bias, stride, "", "silu",
                          with_chain=True)

    # f64 truth in numpy (avoids flipping jax_enable_x64 globally)
    p = (k - 1) // 2
    xp = np.pad(xn.astype(np.float64), ((0, 0), (p, p), (p, p), (0, 0)))
    ho = (xp.shape[1] - k) // stride + 1
    wo = (xp.shape[2] - k) // stride + 1
    z = np.zeros((2, ho, wo, 24))
    for r in range(k):
        for s in range(k):
            z += xp[:, r:r + (ho - 1) * stride + 1:stride,
                    s:s + (wo - 1) * stride + 1:stride] * wn[r, s]
    u = z * sn + bn
    truth = u / (1.0 + np.exp(-u))

    spac = np.spacing(np.maximum(np.asarray(chain, np.float32), 1e-30)
                      .astype(np.float32))
    e_fused = np.abs(np.asarray(y, np.float64) - truth) / spac
    e_xla = np.abs(np.asarray(ref, np.float64) - truth) / spac
    assert e_fused.max() <= e_xla.max() + 1.0, (
        f"fused {e_fused.max():.2f} vs xla {e_xla.max():.2f} "
        "spacing(chain) units from f64 truth")


@pytest.mark.parametrize("k,stride,pad", [(3, 1, ""), (5, 2, "same"),
                                          (3, 2, 1)])
def test_vjp_parity(k, stride, pad):
    """dx/dw (Pallas kernels) and dscale/dbias (XLA reductions) against
    autodiff of the stock composition — reassociation tolerance."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 12, 10, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, 16)) * 0.2, jnp.float32)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, 16), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.2, 0.2, 16), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((1,)), jnp.float32)  # nontrivial

    def f_fused(x, w, s, b):
        y = fused_depthwise(x, w, s, b, stride=stride, padding=pad,
                            act="silu")
        return jnp.sum(y * jnp.cos(y.astype(jnp.float32) + ct))

    def f_ref(x, w, s, b):
        y = _xla_ref(x, w, s, b, stride, pad, "silu")
        return jnp.sum(y * jnp.cos(y.astype(jnp.float32) + ct))

    g_fused = jax.grad(f_fused, argnums=(0, 1, 2, 3))(x, w, scale, bias)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2, 3))(x, w, scale, bias)
    for name, a, b in zip(("dx", "dw", "dscale", "dbias"), g_fused, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5,
            atol=2e-5 * max(1.0, float(jnp.abs(b).max())), err_msg=name)


def test_forward_parity_bf16_grads_finite_and_close():
    """bf16 inputs: grads flow (f32 accumulation inside) and track the
    f32 reference within bf16-rounding error."""
    rng = np.random.default_rng(3)
    xf = rng.standard_normal((2, 9, 9, 8)).astype(np.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 8)) * 0.2, jnp.float32)

    def f(x):
        return jnp.sum(fused_depthwise(x, w, None, None, stride=1,
                                       padding="", act="silu")
                       .astype(jnp.float32) ** 2)

    g16 = jax.grad(f)(jnp.asarray(xf, jnp.bfloat16)).astype(jnp.float32)
    g32 = jax.grad(f)(jnp.asarray(xf))
    assert np.isfinite(np.asarray(g16)).all()
    np.testing.assert_allclose(np.asarray(g16), np.asarray(g32),
                               rtol=0.05, atol=0.05)


def test_identity_affine_and_acts():
    """scale/bias None = identity affine; every FUSED_DW_ACTS epilogue."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 8)) * 0.3, jnp.float32)
    ones = jnp.ones((8,), jnp.float32)
    zeros = jnp.zeros((8,), jnp.float32)
    for act in FUSED_DW_ACTS:
        y = fused_depthwise(x, w, None, None, stride=1, padding="", act=act)
        ref, chain = _xla_ref(x, w, ones, zeros, 1, "", act,
                              with_chain=True)
        _assert_ulp(y, ref, chain, n_round=11)


def test_hwio_kernel_layout_accepted():
    """The (kh, kw, 1, C) HWIO depthwise layout (what Conv2d stores) and
    the squeezed (kh, kw, C) layout must agree bitwise."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 8)) * 0.3, jnp.float32)
    a = fused_depthwise(x, w, None, None, padding="", act="none")
    b = fused_depthwise(x, w.reshape(3, 3, 1, 8), None, None, padding="",
                        act="none")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_eligibility_gate():
    """Blocks route through the fused op only where its contract holds."""
    assert fused_dw_eligible(3, 1, 1, "bn")
    assert fused_dw_eligible(5, 1, 2, "bn")
    assert not fused_dw_eligible([3, 5], 1, 1, "bn")   # MixedConv arms
    assert not fused_dw_eligible(3, 2, 1, "bn")        # dilation
    assert not fused_dw_eligible(3, 1, 4, "bn")        # exotic stride
    assert not fused_dw_eligible(3, 1, 1, "split2")    # AdvProp split BN


# ---------------------------------------------------------------------------
# model-level golden-params equivalence (one shared variable tree applied
# to every variant — a rewrite may not change what the params MEAN)
# ---------------------------------------------------------------------------

def _variants(model_name, **extra):
    kw = dict(num_classes=3, in_chans=3, **extra)
    stock = create_model(model_name, **kw)
    fused = create_model(model_name, fused_depthwise="pallas", **kw)
    s2d = create_model(model_name, stem_s2d=True, **kw)
    return stock, fused, s2d


class TestModelEquivalence:
    @pytest.fixture(scope="class")
    def setup(self):
        stock, fused, s2d = _variants("mnasnet_small")
        v = init_model(stock, jax.random.PRNGKey(0), (1, 32, 32, 3))
        x = jnp.asarray(
            np.random.default_rng(1).uniform(-2, 2, (2, 32, 32, 3)),
            jnp.float32)
        return stock, fused, s2d, v, x

    def test_param_tree_identical(self, setup):
        stock, fused, s2d, v, _ = setup
        vf = init_model(fused, jax.random.PRNGKey(0), (1, 32, 32, 3))
        vs = init_model(s2d, jax.random.PRNGKey(0), (1, 32, 32, 3))
        assert jax.tree_util.tree_structure(v) \
            == jax.tree_util.tree_structure(vf) \
            == jax.tree_util.tree_structure(vs)
        for a, b, c in zip(jax.tree.leaves(v), jax.tree.leaves(vf),
                           jax.tree.leaves(vs)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    def test_eval_outputs_match(self, setup):
        stock, fused, s2d, v, x = setup
        y0 = stock.apply(v, x, training=False)
        yf = fused.apply(v, x, training=False)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(y0),
                                   rtol=1e-5, atol=1e-5)

    # Train-mode full-model comparisons use batch 16: with batch 2, BN
    # batch variances are (a-b)²/4 pair differences — near-cancelling after
    # a few normalized layers — and the comparison's conditioning collapses
    # (a ONE-ulp input perturbation already moves the stock model's global
    # gradient 2.6%; any reassociated-but-correct kernel drifts similarly).
    # At batch 16 the same stock-vs-fused comparison lands at ~2e-5, below
    # the one-ulp noise floor, so tight tolerances are meaningful.
    _XTRAIN = jnp.asarray(
        np.random.default_rng(9).uniform(-2, 2, (16, 32, 32, 3)),
        jnp.float32)

    def test_train_outputs_and_bn_stats_match(self, setup):
        stock, fused, _, v, _ = setup
        x = self._XTRAIN
        r = {"dropout": jax.random.PRNGKey(2)}
        y0, s0 = stock.apply(v, x, training=True, mutable=["batch_stats"],
                             rngs=r)
        yf, sf = fused.apply(v, x, training=True, mutable=["batch_stats"],
                             rngs=r)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(y0),
                                   rtol=1e-3, atol=1e-4)
        for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(sf)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-5)

    @pytest.mark.slow   # tier-1 budget: whole-model interpret-mode grads (~39s);
    # the per-kernel vjp parity sweep keeps gradient coverage fast
    def test_train_grads_match(self, setup):
        stock, fused, _, v, _ = setup
        x = self._XTRAIN

        def loss(params, model):
            y = model.apply(
                {"params": params, "batch_stats": v["batch_stats"]}, x,
                training=True, mutable=["batch_stats"],
                rngs={"dropout": jax.random.PRNGKey(2)})[0]
            return jnp.mean(y ** 2)

        g0 = jax.grad(loss)(v["params"], stock)
        gf = jax.grad(loss)(v["params"], fused)
        flat0 = np.concatenate([np.asarray(l, np.float64).ravel()
                                for l in jax.tree.leaves(g0)])
        flatf = np.concatenate([np.asarray(l, np.float64).ravel()
                                for l in jax.tree.leaves(gf)])
        gnorm = np.linalg.norm(flat0)
        g_rel = np.linalg.norm(flat0 - flatf) / gnorm
        assert g_rel < 5e-4, g_rel
        # Per-leaf: BN-bias grads are batch×spatial sums of dy that cancel
        # to ~1e-8 of the global gradient scale; their "relative" error is
        # cancellation residue, not kernel error. Floor the denominator at
        # a small fraction of the global scale so negligible leaves are
        # held to an absolute bound instead.
        for (p, a), b in zip(
                jax.tree_util.tree_flatten_with_path(g0)[0],
                jax.tree.leaves(gf)):
            an, bn = np.asarray(a, np.float64), np.asarray(b, np.float64)
            denom = max(np.linalg.norm(an.ravel()), 1e-4 * gnorm)
            rel = np.linalg.norm((an - bn).ravel()) / denom
            assert rel < 5e-3, (jax.tree_util.keystr(p), rel)

    @pytest.mark.parametrize("block_kw", [
        dict(kind="dsc", stride=1, dw_kernel_size=3),
        dict(kind="dsc", stride=2, dw_kernel_size=5, se_ratio=0.25),
        dict(kind="ir", stride=1, dw_kernel_size=3, exp_ratio=3.0),
        dict(kind="ir", stride=2, dw_kernel_size=5, exp_ratio=6.0,
             se_ratio=0.25),
    ])
    def test_block_train_parity(self, block_kw):
        """The TIGHT train-mode statement, per block (no BN amplification
        chain): outputs, updated batch_stats and grads of the fused path
        match the stock path at reassociation tolerance."""
        from deepfake_detection_tpu.models.efficientnet_blocks import (
            DepthwiseSeparableConv, InvertedResidual)
        kw = dict(block_kw)
        kind = kw.pop("kind")
        exp = kw.pop("exp_ratio", None)
        cls = DepthwiseSeparableConv if kind == "dsc" else InvertedResidual
        if exp is not None:
            kw["exp_ratio"] = exp
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.standard_normal((4, 16, 16, 8)), jnp.float32)
        stock = cls(out_chs=8, act="silu", **kw)
        fused = cls(out_chs=8, act="silu", fused_depthwise="pallas", **kw)
        v = stock.init(jax.random.PRNGKey(0), x, training=False)

        y0, s0 = stock.apply(v, x, training=True, mutable=["batch_stats"])
        yf, sf = fused.apply(v, x, training=True, mutable=["batch_stats"])
        np.testing.assert_allclose(np.asarray(yf), np.asarray(y0),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(sf)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

        def loss(params, model):
            y = model.apply(
                {"params": params, "batch_stats": v["batch_stats"]}, x,
                training=True, mutable=["batch_stats"])[0]
            return jnp.sum(y ** 2)

        g0 = jax.grad(loss)(v["params"], stock)
        gf = jax.grad(loss)(v["params"], fused)
        # grads through batch-stat BN pass d rsqrt(var+eps) — reassoc
        # noise in var is amplified by (var+eps)^-1.5, hence the wider rtol
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(gf)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-2, atol=1e-4)

    def test_swish_se_family_eval_parity(self):
        """efficientnet_b0: swish epilogue + SE between dw and pw."""
        stock, fused, _ = _variants("efficientnet_b0")
        v = init_model(stock, jax.random.PRNGKey(0), (1, 32, 32, 3))
        x = jnp.asarray(
            np.random.default_rng(4).uniform(-2, 2, (1, 32, 32, 3)),
            jnp.float32)
        y0 = stock.apply(v, x, training=False)
        yf = fused.apply(v, x, training=False)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(y0),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# space-to-depth stem
# ---------------------------------------------------------------------------

class TestStemS2d:
    def test_space_to_depth_roundtrip(self):
        """depth_to_space inverts the loader shuffle exactly — the trainer
        relies on it to un-shuffle ``--save-images`` dumps under s2d."""
        from deepfake_detection_tpu.ops.conv import depth_to_space
        x = np.random.default_rng(5).standard_normal(
            (2, 8, 6, 3)).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(depth_to_space(space_to_depth(jnp.asarray(x)))), x)

    def test_weight_rescatter_is_lossless(self):
        """The (3,3,C,O) → (2,2,4C,O) rewrite is a pure scatter: every
        original weight appears exactly once, bit-identical, zeros
        elsewhere — so checkpoints convert with NO numeric change."""
        rng = np.random.default_rng(0)
        kern = jnp.asarray(rng.standard_normal((3, 3, 5, 7)), jnp.float32)
        for pad_type, off in (("", 1), ("same", 0)):
            k2, pad = space_to_depth_stem_kernel(kern, pad_type)
            assert k2.shape == (2, 2, 20, 7)
            # invert: (2,2,2,2,C,O) block layout back to the 4x4 embedding
            k4 = np.asarray(k2).reshape(2, 2, 2, 2, 5, 7) \
                .transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 5, 7)
            np.testing.assert_array_equal(k4[off:off + 3, off:off + 3],
                                          np.asarray(kern))
            mask = np.ones((4, 4), bool)
            mask[off:off + 3, off:off + 3] = False
            assert (k4[mask] == 0).all()
            assert np.count_nonzero(k4) == np.count_nonzero(
                np.asarray(kern))
            assert pad == [(1, 0), (1, 0)] if pad_type == "" \
                else [(0, 1), (0, 1)]

    @pytest.mark.parametrize("pad_type", ["", "same"])
    def test_stem_conv_parity(self, pad_type):
        """stride-2 3×3 conv == stride-1 2×2 conv over s2d input: same
        taps, same products, float reassociation only."""
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((2, 16, 16, 3)), jnp.float32)
        kern = jnp.asarray(rng.standard_normal((3, 3, 3, 8)) * 0.2,
                           jnp.float32)
        pad = [(1, 1), (1, 1)] if pad_type == "" else "SAME"
        ref = lax.conv_general_dilated(
            x, kern, (2, 2), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        k2, bpad = space_to_depth_stem_kernel(kern, pad_type)
        got = lax.conv_general_dilated(
            space_to_depth(x), k2, (1, 1), bpad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    def test_space_to_depth_layout(self):
        """(di, dj, c)-major channel order — the order the kernel rewrite
        assumes."""
        x = jnp.arange(2 * 4 * 4 * 3, dtype=jnp.float32) \
            .reshape(2, 4, 4, 3)
        y = space_to_depth(x)
        assert y.shape == (2, 2, 2, 12)
        np.testing.assert_array_equal(
            np.asarray(y[0, 0, 0]),
            np.concatenate([np.asarray(x[0, di, dj])
                            for di in range(2) for dj in range(2)]))

    def test_model_golden_params_equivalence(self):
        """One variable tree, three input paths: stock, s2d raw-input
        (in-model shuffle), s2d loader-preshuffled — and preshuffled must
        be EXACTLY the in-model result (same conv, same order)."""
        stock, _, s2d = _variants("mnasnet_small")
        v = init_model(stock, jax.random.PRNGKey(0), (1, 32, 32, 3))
        x = jnp.asarray(
            np.random.default_rng(2).uniform(-2, 2, (2, 32, 32, 3)),
            jnp.float32)
        y0 = stock.apply(v, x, training=False)
        ys = s2d.apply(v, x, training=False)
        yp = s2d.apply(v, space_to_depth(x), training=False)
        np.testing.assert_allclose(np.asarray(ys), np.asarray(y0),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(ys), np.asarray(yp))

    def test_odd_input_rejected(self):
        with pytest.raises(AssertionError, match="divisible"):
            space_to_depth(jnp.zeros((1, 5, 4, 3)))
        with pytest.raises(ValueError, match="3x3"):
            space_to_depth_stem_kernel(jnp.zeros((5, 5, 3, 8)))


# ---------------------------------------------------------------------------
# loader-side pixel shuffle (DeviceLoader prologue)
# ---------------------------------------------------------------------------

def test_loader_prologue_s2d(tmp_path):
    from PIL import Image

    from deepfake_detection_tpu.data import FolderDataset, create_loader

    rng = np.random.default_rng(0)
    for cls in ("a", "b"):
        d = tmp_path / "imgs" / cls
        os.makedirs(d)
        for i in range(4):
            Image.fromarray(rng.integers(0, 255, (64, 64, 3),
                                         dtype=np.uint8).astype(np.uint8)
                            ).save(d / f"{i}.jpg")

    def batch(stem_s2d):
        ds = FolderDataset(str(tmp_path / "imgs"))
        loader = create_loader(ds, (3, 64, 64), batch_size=4,
                               is_training=False, dtype=jnp.float32,
                               stem_s2d=stem_s2d)
        x, *_ = next(iter(loader))
        return np.asarray(x)

    plain = batch(False)
    shuffled = batch(True)
    assert plain.shape == (4, 64, 64, 3)
    assert shuffled.shape == (4, 32, 32, 12)
    from deepfake_detection_tpu.ops.conv import space_to_depth as s2d_op
    np.testing.assert_array_equal(shuffled,
                                  np.asarray(s2d_op(jnp.asarray(plain))))


def test_fused_step_under_local_bn_mesh():
    """The runner's DEFAULT multi-device path is the unified GSPMD jit
    with local-BN stat grouping (ISSUE 12; it was a shard_map wrapper
    before — where pallas_call historically tripped the replication
    checker).  Route one fused step through that exact path on the
    8-device unified mesh and hold it to the stock step's numbers —
    pinning that interpret-mode pallas_call partitions under GSPMD."""
    from deepfake_detection_tpu.parallel import batch_sharding, \
        make_train_mesh
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_train_step)
    from deepfake_detection_tpu.losses import cross_entropy
    import optax

    mesh = make_train_mesh()
    x = jax.device_put(
        np.random.default_rng(3).uniform(-2, 2, (8, 32, 32, 3))
        .astype(np.float32), batch_sharding(mesh))
    y = jax.device_put(np.arange(8, dtype=np.int64) % 3,
                       batch_sharding(mesh))
    losses = {}
    for label, extra in (("stock", {}),
                         ("fused", {"fused_depthwise": "pallas"})):
        m = create_model("mnasnet_small", num_classes=3, in_chans=3, **extra)
        v = init_model(m, jax.random.PRNGKey(0), (2, 32, 32, 3),
                       training=True)
        state = create_train_state(v, optax.sgd(1e-3))
        step = make_train_step(m, optax.sgd(1e-3), cross_entropy,
                               mesh=mesh, bn_mode="local", donate=False)
        new_state, metrics = step(state, x, y, jax.random.PRNGKey(1))
        assert np.isfinite(float(metrics["loss"]))
        assert int(new_state.step) == 1
        losses[label] = float(metrics["loss"])
    np.testing.assert_allclose(losses["fused"], losses["stock"],
                               rtol=5e-5, atol=5e-5)


# ---------------------------------------------------------------------------
# the default path's depthwise stage: XLA's conv and dx, the kernel's dW
# (ops/conv.py:Conv2d -> ops/depthwise_pallas.py:depthwise_conv, PR 29)
# ---------------------------------------------------------------------------

# (k, stride, C, H=W): every kernel x stride at each channel class (a
# fraction of the lanes, lanes and a half, two and a quarter), odd sizes
# that leave a last H tile short (19 of the flagship's, 13 and 11 for its
# 38 and 75 cut to test size)
_DEFAULT_STAGES = [(k, s, c, hw)
                   for k in (3, 5) for s in (1, 2)
                   for c, hw in ((32, 19), (192, 13), (288, 11))]


def _stage_vjp(conv, variables, x, g, impl):
    """(y, dW, dx) of one depthwise stage under the kernel's filter
    gradient (interpreted here) or under the parent's nn.Conv path."""
    platform = "tpu" if impl == "kernel" else "cpu"
    with dw_grad_scope(1, platform=platform):
        y, vjp = jax.vjp(lambda v, x: conv.apply(v, x), variables, x)
        dv, dx = vjp(g.astype(y.dtype))
    return y, dv["params"]["conv"]["kernel"], dx


@pytest.mark.parametrize("pad", ["", "same"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("k,stride,c,hw", _DEFAULT_STAGES)
def test_default_path_filter_grad(k, stride, c, hw, dtype, pad):
    """Same parameter tree and init; forward and dx the parent's bit for
    bit; dW against the float32 / ``highest`` gradient at reassociation
    tolerance, and no further from it than the parent's own."""
    from deepfake_detection_tpu.ops.conv import create_conv2d
    conv = create_conv2d(c, k, stride=stride, padding=pad, depthwise=True,
                         dtype=dtype, name="conv_dw")
    rng = np.random.default_rng(k * 100 + stride * 10 + c)
    x = jnp.asarray(rng.standard_normal((3, hw, hw, c)), dtype)
    v = conv.init(jax.random.PRNGKey(0), x)
    with dw_grad_scope(1, platform="tpu"):
        v_kernel = conv.init(jax.random.PRNGKey(0), x)
    assert jax.tree.structure(v) == jax.tree.structure(v_kernel)
    for a, b in zip(jax.tree.leaves(v), jax.tree.leaves(v_kernel)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    ho = conv.apply(v, x).shape[1]
    g = jnp.asarray(rng.standard_normal((3, ho, ho, c)), dtype)
    y0, dw0, dx0 = _stage_vjp(conv, v, x, g, "xla")
    y1, dw1, dx1 = _stage_vjp(conv, v, x, g, "kernel")
    np.testing.assert_array_equal(np.asarray(y0, np.float32),
                                  np.asarray(y1, np.float32))
    np.testing.assert_array_equal(np.asarray(dx0, np.float32),
                                  np.asarray(dx1, np.float32))
    assert dw1.dtype == dw0.dtype == jnp.float32 and dw1.shape == dw0.shape

    padv = _resolve(pad, k, stride, hw, hw)
    ref = jax.vjp(lambda w: lax.conv_general_dilated(
        x.astype(jnp.float32), w, (stride, stride), padv,
        feature_group_count=c, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST),
        v["params"]["conv"]["kernel"])[1](g.astype(jnp.float32))[0]
    scale = max(1.0, float(jnp.abs(ref).max()))
    np.testing.assert_allclose(np.asarray(dw1), np.asarray(ref), rtol=2e-5,
                               atol=2e-5 * scale)
    assert float(jnp.abs(dw1 - ref).max()) <= \
        float(jnp.abs(dw0 - ref).max()) + 2e-5 * scale


# every depthwise stage of the two benchmark configurations:
# (batch, H=W, C, k, stride); benchmark/configs/*.json build exactly these
# (test_dw_grad_census_of_the_benchmark_configurations)
_FLAGSHIP_STAGES = [
    (3, 300, 256, 3, 1), (3, 300, 32, 3, 1), (3, 300, 192, 3, 2),
    (3, 150, 288, 3, 1), (3, 150, 288, 5, 2), (3, 75, 480, 5, 1),
    (3, 75, 480, 3, 2), (3, 38, 960, 3, 1), (3, 38, 960, 5, 1),
    (3, 38, 1344, 5, 1), (3, 38, 1344, 5, 2), (3, 19, 2304, 5, 1),
    (3, 19, 2304, 3, 1), (3, 19, 3840, 3, 1)]
_B4_STAGES = [
    (80, 190, 48, 3, 1), (80, 190, 24, 3, 1), (80, 190, 144, 3, 2),
    (80, 95, 192, 3, 1), (80, 95, 192, 5, 2), (80, 48, 336, 5, 1),
    (80, 48, 336, 3, 2), (80, 24, 672, 3, 1), (80, 24, 672, 5, 1),
    (80, 24, 960, 5, 1), (80, 24, 960, 5, 2), (80, 12, 1632, 5, 1),
    (80, 12, 1632, 3, 1), (80, 12, 2688, 3, 1)]


@pytest.mark.parametrize(
    "stage,expected",
    [(s, "kernel") for s in _FLAGSHIP_STAGES]
    + [(s, "xla") for s in _B4_STAGES],
    ids=lambda v: v if isinstance(v, str) else "x".join(map(str, v)))
def test_dw_grad_impl_follows_the_probe(stage, expected):
    """PERF.md section 6 (PR 29): the kernel at every flagship stage, XLA's
    own gradient at every B4 stage; XLA's wherever more than one device
    compiles the step, and wherever the kernel could only be interpreted."""
    b, hw, c, k, stride = stage
    shape = (b, hw, hw, c)
    assert dw_grad_impl(shape, k, stride, jnp.bfloat16, devices=1,
                        platform="tpu") == expected
    for devices in (2, 4, 256):
        assert dw_grad_impl(shape, k, stride, jnp.bfloat16, devices=devices,
                            platform="tpu") == "xla"
    assert dw_grad_impl(shape, k, stride, jnp.bfloat16, devices=1,
                        platform="cpu") == "xla"
    # with nothing said: this process's backend, which is the CPU's
    assert dw_grad_impl(shape, k, stride, jnp.bfloat16) == "xla"


@pytest.mark.parametrize("config,stages,count", [
    ("flagship_v4_600", _FLAGSHIP_STAGES, 55),
    ("effnet_b4_380", _B4_STAGES, 32)])
def test_dw_grad_census_of_the_benchmark_configurations(config, stages,
                                                        count):
    """One abstract trace of the model a cell trains finds its depthwise
    stages: the shapes above, 55 / 0 and 0 / 32 on one chip, and XLA's
    gradient everywhere on four."""
    import json
    from deepfake_detection_tpu.config import TrainConfig
    from deepfake_detection_tpu.runners import train as T
    repo = os.path.join(os.path.dirname(__file__), os.pardir)
    with open(os.path.join(repo, "benchmark", "configs",
                           config + ".json")) as f:
        conf = json.load(f)
    cfg = TrainConfig.from_args(list(conf["train_flags"]))
    c, h, w = conf["input_size"]
    model = T.build_model(cfg, c)
    shape = (cfg.batch_size, h, w, c)
    one = dw_grad_census(model, shape, jnp.bfloat16, 1, "tpu")
    impl = "kernel" if stages is _FLAGSHIP_STAGES else "xla"
    assert {k: len(v) for k, v in one.items()} == {impl: count}
    assert {(s[0], s[1], s[3], k, st) for s, k, st in one[impl]} == \
        set(stages)
    four = dw_grad_census(model, shape, jnp.bfloat16, 4, "tpu")
    assert {k: len(v) for k, v in four.items()} == {"xla": count}


class _OneBlock(nn.Module):
    """A flagship-shaped MBConv (k5, expansion 6, SE, swish) and a head."""

    @nn.compact
    def __call__(self, x, training: bool = False):
        from deepfake_detection_tpu.models.efficientnet_blocks import \
            InvertedResidual
        x = InvertedResidual(8, dw_kernel_size=5, exp_ratio=6.0,
                             se_ratio=0.25, act="swish",
                             name="blocks_0_0")(x, training=training)
        return nn.Dense(2, name="classifier")(x.mean(axis=(1, 2)))


def test_default_path_train_step_takes_the_kernel_on_one_device():
    """``make_train_step`` tells the stage how many devices compile it: on
    a one-device mesh the block trains through the kernel (interpreted
    here) to the parent's loss, parameter tree and, at reassociation
    tolerance, parameters; over eight devices it keeps XLA's gradient."""
    import optax
    from deepfake_detection_tpu.losses import cross_entropy
    from deepfake_detection_tpu.parallel import make_train_mesh
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_train_step)
    model = _OneBlock()
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((3, 11, 11, 8)), jnp.float32)
    y = jnp.asarray([0, 1, 1])

    def fresh_state():      # create_train_state consumes its variables
        return create_train_state(
            model.init(jax.random.PRNGKey(0), x, training=True),
            optax.sgd(0.1))
    out = {}
    for impl, platform in (("xla", "cpu"), ("kernel", "tpu")):
        census = {}
        step = make_train_step(
            model, optax.sgd(0.1), cross_entropy,
            mesh=make_train_mesh(devices=jax.devices()[:1]), donate=False)
        state = fresh_state()
        with dw_grad_scope(1, platform=platform, census=census):
            state, metrics = step(state, x, y, jax.random.PRNGKey(1))
        assert {k: len(s) for k, s in census.items()} == {impl: 1}
        out[impl] = (float(metrics["loss"]), state.params)
    assert out["kernel"][0] == out["xla"][0]
    flat = {impl: jax.tree_util.tree_leaves_with_path(p)
            for impl, (_, p) in out.items()}
    assert [k for k, _ in flat["kernel"]] == [k for k, _ in flat["xla"]]
    for (path, a), (_, b) in zip(flat["kernel"], flat["xla"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5, err_msg=str(path))
    # the runner's default mesh here: eight devices compile the step
    census = {}
    step = make_train_step(model, optax.sgd(0.1), cross_entropy,
                           mesh=make_train_mesh(), bn_mode="global",
                           donate=False)
    x8, state = jnp.concatenate([x, x, x])[:8], fresh_state()
    with dw_grad_scope(1, platform="tpu", census=census):
        step(state, x8, jnp.asarray([0, 1] * 4), jax.random.PRNGKey(1))
    assert {k: len(s) for k, s in census.items()} == {"xla": 1}
