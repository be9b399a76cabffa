"""ops/moe.py (a routed expert layer held in part): both forms of its grouped
products (``jax.lax.ragged_dot``; the megablox kernels, interpreted) against
the plain loop over the experts, forward and gradients; the shares of a
deployment add up to the uncut layer; no assignment is dropped, up to every
token choosing held experts; the bias selects and does not weigh; the counts
made on the device are the routing's.

Sizes: d 64, 8 experts of 32, top-2, 96 tokens, float32 (the interpreted
kernels multiply in float32 too, so everything agrees to the order of the
sums).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepfake_detection_tpu.ops import moe as M      # noqa: E402

T, D, F, E, K = 96, 64, 32, 8, 2
IMPLS = ["xla", "pallas"]


def _inputs(seed=0, bias_std=0.3):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"z": jax.random.normal(k[0], (T, D)),
            "gate": jax.random.normal(k[1], (D, E)) / 8,
            "bias": bias_std * jax.random.normal(k[2], (E,)),
            "w13": jax.random.normal(k[3], (E, D, 2 * F)) / 8,
            "w2": jax.random.normal(k[4], (E, F, D)) / 6}


def plain(z, gate, bias, w13, w2, held):
    """Every held expert on every token, times the token's weight for it."""
    r = M.route(z @ gate, bias, K)
    out = jnp.zeros_like(z)
    for e in range(held[0], held[0] + held[1]):
        w = jnp.sum(jnp.where(r.sel == e, r.weight, 0.0), axis=-1)
        h = z @ w13[e]
        out = out + w[:, None] * ((jax.nn.silu(h[:, :F]) * h[:, F:]) @ w2[e])
    return out


def routed(z, gate, bias, w13, w2, held, impl, full=False):
    """The op's result; with ``full`` its word on the capacity it took."""
    r = M.route(z @ gate, bias, K)
    first, count = held
    y, took_all = M.expert_ffn(z, r, w13[first:first + count],
                               w2[first:first + count], held, E, impl=impl)
    return (y, int(took_all)) if full else y


def _close(a, b, tol=2e-5):
    a, b = (np.asarray(v, np.float32) for v in (a, b))
    np.testing.assert_allclose(a, b, rtol=tol,
                               atol=tol * float(np.max(np.abs(b))))


# the first capacity over the uniform share: the op's own 2 (one capacity
# with all eight held, the first of two with two held: the seeded routing
# brings them 2 and 32 of 192 rows), 8 (one capacity whatever is held), and
# a half (all eight held overflow the first of two, as do experts 6-7 in the
# array form, whose capacity is not rounded up to a row tile)
@pytest.mark.parametrize("headroom", [2.0, 8.0, 0.5],
                         ids=["twice-uniform", "all", "half-uniform"])
@pytest.mark.parametrize("held", [(0, 8), (2, 2), (6, 2)],
                         ids=["all-held", "experts-2-3", "experts-6-7"])
@pytest.mark.parametrize("impl", IMPLS)
def test_both_forms_equal_the_plain_expert_loop(impl, held, headroom,
                                                monkeypatch):
    monkeypatch.setattr(M, "_HEADROOM", headroom)
    a = _inputs()
    args = (a["z"], a["gate"], a["w13"], a["w2"])

    def loss(fn, z, gate, w13, w2):
        return jnp.sum(fn(z, gate, a["bias"], w13, w2) ** 2)
    want = plain(a["z"], a["gate"], a["bias"], a["w13"], a["w2"], held)
    got, full = routed(a["z"], a["gate"], a["bias"], a["w13"], a["w2"], held,
                       impl, full=True)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    _close(got, want)
    # the op's word is the routing's: every row where the assignments on
    # held experts pass the first capacity, or there is one capacity
    sel = np.asarray(M.route(a["z"] @ a["gate"], a["bias"], K).sel)
    total = int(((sel >= held[0]) & (sel < held[0] + held[1])).sum())
    caps = M._capacities(T * K, held[1], E, 64 if impl == "pallas" else 1)
    assert full == int(len(caps) == 1 or total > caps[0])
    g_want = jax.grad(lambda *x: loss(
        lambda *y: plain(*y, held), *x), argnums=(0, 1, 2, 3))(*args)
    g_got = jax.grad(lambda *x: loss(
        lambda *y: routed(*y, held, impl), *x),
        argnums=(0, 1, 2, 3))(*args)
    for got_leaf, want_leaf in zip(g_got, g_want):
        _close(got_leaf, want_leaf)


@pytest.mark.parametrize("impl", IMPLS)
def test_the_shares_add_up_to_the_uncut_layer(impl):
    """held = (0,2), (2,2), (4,2), (6,2) summed are the layer with all eight
    held: the router and the normalisation are counted once, by each."""
    a = _inputs(1)
    whole = routed(**a, held=(0, 8), impl=impl)
    parts = sum(routed(**a, held=(first, 2), impl=impl)
                for first in (0, 2, 4, 6))
    _close(parts, whole)
    _close(whole, plain(**a, held=(0, 8)))


@pytest.mark.parametrize("impl", IMPLS)
def test_every_token_choosing_held_experts_loses_nothing(impl):
    """A bias that sends both selections of every token to the two held
    experts: T x k assignments, the last capacity, no row dropped."""
    a = _inputs(2)
    a["bias"] = jnp.where(jnp.arange(E) < 2, 10.0, 0.0)
    r = M.route(a["z"] @ a["gate"], a["bias"], K)
    assert np.all(np.sort(np.asarray(r.sel), axis=1) == [0, 1])
    got, full = routed(**a, held=(0, 2), impl=impl, full=True)
    counts = np.asarray(M.routing_counts(r.sel, (0, 2), full))
    assert counts.tolist() == [T, T * K, T, 2 * T, 1]
    _close(got, plain(**a, held=(0, 2)))
    # and none of them held: nothing comes back, and nothing fails
    got, full = routed(**a, held=(4, 2), impl=impl, full=True)
    assert not np.any(np.asarray(got)) and full == 0


def test_the_bias_changes_the_selection_and_not_the_weight():
    a = _inputs(3)
    logits = a["z"] @ a["gate"]
    s = np.asarray(jax.nn.sigmoid(logits), np.float64)
    with_bias = M.route(logits, a["bias"], K)
    without = M.route(logits, jnp.zeros((E,)), K)
    sel = np.asarray(with_bias.sel)
    moved = np.any(np.sort(sel, 1) != np.sort(np.asarray(without.sel), 1), 1)
    assert 0 < moved.sum() < T
    # selected by s + bias ...
    biased = s + np.asarray(a["bias"], np.float64)
    assert np.array_equal(np.sort(sel, 1),
                          np.sort(np.argsort(-biased, 1)[:, :K], 1))
    # ... weighed by s alone, normalised over the selected
    picked = np.take_along_axis(s, sel, 1)
    np.testing.assert_allclose(
        np.asarray(with_bias.weight),
        picked / (picked.sum(1, keepdims=True) + 1e-6), rtol=1e-5)
    scaled = M.route(logits, a["bias"], K, scale=2.5)
    np.testing.assert_allclose(np.asarray(scaled.weight),
                               2.5 * np.asarray(with_bias.weight), rtol=1e-6)


@pytest.mark.parametrize("held", [(0, 8), (2, 2), (5, 3)])
def test_the_device_counts_equal_the_routings(held):
    a = _inputs(4)
    sel = np.asarray(M.route(a["z"] @ a["gate"], a["bias"], K).sel)
    per_expert = np.bincount(sel.reshape(-1), minlength=E)[
        held[0]:held[0] + held[1]]
    counts = np.asarray(M.routing_counts(jnp.asarray(sel), held))
    assert counts.dtype == np.int32
    assert counts.tolist() == [T, per_expert.sum(), per_expert.max(),
                               per_expert.max() * held[1], 0]


def test_what_impl_none_chooses():
    # this process's backend is the CPU: the array form, whatever the shape
    assert M.moe_impl(16384, 4, 2048, 1536) == "xla"
    assert M.moe_census(4, 16384, 4, 2048, 1536) == (0, 4)
    # a described chip: the kernels where their tiles divide the shapes
    assert M.moe_impl(16384, 4, 2048, 1536, backend="tpu") == "pallas"
    assert M.moe_impl(16384, 4, 2048, 1500, backend="tpu") == "xla"
    assert M.moe_impl(100, 2, 2048, 1536, backend="tpu") == "xla"
    a = _inputs(5)
    _close(routed(**a, held=(0, 8), impl=None),
           routed(**a, held=(0, 8), impl="xla"), 0)


def test_the_capacities_are_row_tiles_and_end_with_every_row():
    # the cell's pass: an eighth of the experts held, a quarter of the rows
    assert M._capacities(131072, 8, 64, 512) == (32768, 131072)
    # the uncut model holds every expert, half of them, a quarter
    assert M._capacities(131072, 64, 64, 512) == (131072,)
    assert M._capacities(131072, 32, 64, 512) == (131072,)
    assert M._capacities(131072, 16, 64, 512) == (65536, 131072)
    # rounded up to whole row tiles
    assert M._capacities(192, 2, 8, 64) == (128, 192)
    assert M._capacities(192, 2, 8, 1) == (96, 192)
    assert M._capacities(192, 3, 8, 64) == (192,)
    assert M._tiles(65536, 2048, 3072) == (512, 1024, 1024)
    assert M._tiles(65536, 1536, 2048) == (512, 768, 1024)
    assert M._tiles(192, 64, 64) == (64, 64, 64)
