"""models/keyevl2.py (grouped-query attention over the keys a learned
indexer selects, the indexer's KL loss, a softmax router over routed experts
held in part) and ops/sparse_attention.py against the plain reference
``benchmark/reference/keyevl2.py`` on seeded weights, at tiny sizes on the
CPU: a layer's forward and gradients (array form and the kernels under the
interpreter), the sparse attention against a float64 softmax with -inf off
the selection, the selection against ``lax.top_k`` with ties, the
reference's bands against one band of every key, the holds of the two losses, the eight shares of a 128-expert layer, the published
constructor's size by shape, the planted faults, the scopes, three AdamW
steps through ``make_train_step`` and one tiny run through
``runners/train.py``.

Tolerances: everything here is float32 on the CPU, where the program and the
reference differ by the order of their sums alone (a sorted, grouped product
against every expert on every token; the kernels' running softmax over key
tiles against a whole softmax; the index scores head by head against one
contraction).  A layer's outputs and gradients agree to 2e-4 of their norm
(``LAYER_TOL``), the model's loss to 1e-5 and its gradients to 1e-3, three
steps' parameter changes to 2e-2 (Adam divides by the gradient's own
magnitude).  The seeds are ones on which no selection sits on a rounding
edge: a key that flips in or out of a selection is a step, not a rounding.
The kernels against the array form are held to 1e-5 of the norm where both
see the same selection, and their selections are held equal bit for bit on
index scores that are exact in float32 (small integers), ties and all.
"""

import functools
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import faults_keyevl2 as FAULTS            # noqa: E402
from benchmark.lib import weights as W                        # noqa: E402
from benchmark.reference import keyevl2 as R                  # noqa: E402
from benchmark.reference import optim_adamw as O              # noqa: E402
from deepfake_detection_tpu.models import create_model        # noqa: E402
from deepfake_detection_tpu.models import keyevl2 as K        # noqa: E402

SA = importlib.import_module("deepfake_detection_tpu.ops.sparse_attention")

with open(os.path.join(ROOT, "benchmark", "configs",
                       "keye_vl2_30b_a3b_4l.json")) as _f:
    CELL = json.load(_f)
with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                       "tiny_keyevl2_f32.json")) as _f:
    TINY = json.load(_f)
SPEC = R.model_spec(TINY)
LAYER_TOL = 2e-4
# the tiny layer's widths, as models/keyevl2.py:_TINY has them
WIDTHS = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
              index_heads=3, index_dim=8, index_topk=16, n_layers=4,
              d_expert=32, rope_theta=1e7, eps=1e-6)
INDEXER = ("index_q", "index_k", "index_k_norm", "index_w")


@pytest.fixture(scope="module")
def variables():
    return W.make_variables(7, *R.param_shapes(SPEC), leaf=R.init_leaf)


def _ids(rows=2, l=40, seed=1, vocab=512):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, l), 0, vocab)
    return ids, jnp.concatenate(
        [ids[:, 1:], -jnp.ones((rows, 1), jnp.int32)], 1)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


def _worst(g1, g2):
    return max(_rel(a, b) for a, b in zip(jax.tree.leaves(g1),
                                          jax.tree.leaves(g2)))


# ---- the sizes, by shapes alone ---------------------------------------------

def _count(name):
    m = create_model(name)
    s = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32)))
    return m, s, sum(x.size for x in jax.tree.leaves(s["params"]))


ATTENTION = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 128
INDEXER_N = 2048 * 1024 + 2048 * 64 + 2 * 64 + 2048 * 16
LAYER = ATTENTION + INDEXER_N + 2048 * 128 + 2 * 2048
EXPERT = 3 * 2048 * 768


def test_published_model_follows_the_published_config_and_is_30_64b():
    m, s, n = _count("keye_vl2_30b_a3b")
    assert (ATTENTION, INDEXER_N, LAYER, EXPERT) == \
        (18_874_624, 2_261_120, 21_401_984, 4_718_592)
    assert (m.n_layers, m.vocab_rows, m.held) == (48, 151936, (0, 128))
    assert n == 48 * (LAYER + 128 * EXPERT) + 2 * 151936 * 2048 + 2048 \
        == 30_640_656_384
    assert f"{n:,}" in CELL["source_detail"]
    assert "batch_stats" not in s


def test_the_cut_is_four_published_layers_at_published_widths_and_465m():
    m, s, n = _count("keye_vl2_30b_a3b_4l")
    whole = create_model("keye_vl2_30b_a3b")
    assert (m.n_layers, m.vocab_rows, m.held) == (4, 18992, (0, 16))
    assert n == 465_391_104 == 4 * (LAYER + 16 * EXPERT) \
        + 2 * 18992 * 2048 + 2048
    assert f"{n:,}" in CELL["deployment"]
    sa = CELL["sa_config"]
    for field, key in (("d_model", CELL["hidden_size"]),
                       ("d_expert", CELL["moe_intermediate_size"]),
                       ("n_heads", CELL["num_attention_heads"]),
                       ("n_kv_heads", CELL["num_key_value_heads"]),
                       ("head_dim", CELL["head_dim"]),
                       ("index_heads", sa["indexer_num_heads"]),
                       ("index_dim", sa["indexer_head_dim"]),
                       ("index_topk", sa["topk"]),
                       ("top_k", CELL["num_experts_per_tok"]),
                       ("eps", CELL["rms_norm_eps"]),
                       ("rope_theta", CELL["rope_theta"])):
        assert getattr(m, field) == getattr(whole, field) == key, field
    assert m.n_experts == whole.n_experts == CELL["num_experts_published"] \
        == CELL["num_local_experts"] == 128
    assert m.held == (CELL["held_first"], CELL["num_experts"])
    spec = R.model_spec(CELL)
    assert (spec["held"], spec["experts"], spec["layers"], spec["rows"]) == \
        (m.held, 128, 4, 18992)
    # the reference's tree is the program's, leaf for leaf
    prog = {tuple(k.key for k in path): tuple(x.shape) for path, x in
            jax.tree_util.tree_flatten_with_path(s["params"])[0]}
    assert prog == dict(W._flatten(R.param_shapes(spec)[0]))


def test_the_tiny_model_is_the_cuts_mechanism():
    tiny = create_model("keye_vl2_tiny")
    assert tiny.n_layers == SPEC["layers"] == 4
    assert (tiny.held, tiny.n_experts, tiny.top_k, tiny.index_topk) == \
        (SPEC["held"], SPEC["experts"], SPEC["top_k"], SPEC["topk"]) \
        == ((0, 2), 16, 4, 16)
    assert tiny.dsa_layers() == 4


# ---- a layer: forward and gradient against the reference -------------------

def _layer(**kw):
    kw = dict(dict(n_experts=16, top_k=4, held=(0, 2)), **kw)
    return K._Layer(**WIDTHS, **kw)


def _layer_pair(variables, index, l=40, quant=None, **kw):
    """The program's and the reference's scalar function of one layer: the
    output against fixed weights plus the layer's mean KL."""
    name = f"layers_{index}"
    p = variables["params"][name]
    ks = jax.random.split(jax.random.PRNGKey(index), 2)
    x, w = (jax.random.normal(k, (l, 64)) for k in ks)
    mod = _layer(**kw)

    def prog(p, x):
        y, mut = mod.apply({"params": p}, x[None], False,
                           mutable=["aux_loss"])
        kl = mut["aux_loss"]["dsa_kl"] * WIDTHS["n_layers"]
        return jnp.sum(y[0] * w) + kl

    def ref(p, x):
        y, kl = R.layer_forward(p, x, SPEC, quant)
        return jnp.sum(y * w) + kl
    return prog, ref, p, x


@pytest.mark.parametrize("index,l,kw", [
    (0, 40, {"attn_impl": "full", "moe_impl": "xla"}),
    (1, 128, {"dsa_impl": "pallas", "moe_impl": "xla"}),
    (2, 40, {"attn_impl": "full", "moe_impl": "pallas"})],
    ids=["array-xla", "kernels-xla", "array-megablox"])
def test_each_layer_forward_and_gradient_match_the_reference(variables,
                                                             index, l, kw):
    prog, ref, p, x = _layer_pair(variables, index, l, **kw)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(prog(p, x), ref(p, x), rtol=1e-4)
        assert _worst(jax.grad(prog, (0, 1))(p, x),
                      jax.grad(ref, (0, 1))(p, x)) < LAYER_TOL


def test_one_precision_lower_fails_the_layer_tolerance(variables):
    _, ref, p, x = _layer_pair(variables, 0)
    _, low, _, _ = _layer_pair(variables, 0, quant="bf16")
    with jax.default_matmul_precision("highest"):
        assert _worst(jax.grad(low, (0, 1))(p, x),
                      jax.grad(ref, (0, 1))(p, x)) > 5 * LAYER_TOL


# ---- the op against the equations ------------------------------------------

def _op_inputs(seed, l=256, h=4, hk=2, d=16, nj=3, e=8, integer=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(kk, s) for kk, s in zip(
        ks[:3], ((1, l, h, d), (1, l, hk, d), (1, l, hk, d))))
    if integer:
        # small integers: every index score exact in float32, ties and all
        qi, ki, w = (jax.random.randint(kk, s, -2, 3).astype(jnp.float32)
                     for kk, s in zip(ks[3:], ((1, l, nj, e), (1, l, e),
                                               (1, l, nj))))
        qi = qi * 0.5
    else:
        qi, ki, w = (jax.random.normal(kk, s) for kk, s in zip(
            ks[3:], ((1, l, nj, e), (1, l, e), (1, l, nj))))
    return q, k, v, qi, ki, w


def _numpy_selection(qi, ki, w, topk):
    """The selection from the equations: per query, the topk causal keys of
    largest score by a stable sort (ties to the lower index)."""
    qi, ki, w = (np.asarray(a, np.float64)[0] for a in (qi, ki, w))
    l = qi.shape[0]
    index = np.einsum("tj,tjs->ts", w, np.maximum(
        np.einsum("tje,se->tjs", qi, ki), 0.0))
    sel = np.zeros((l, l), bool)
    for t in range(l):
        order = np.argsort(-index[t, :t + 1], kind="stable")
        sel[t, order[:topk]] = True
    return index, sel


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_selection_is_lax_top_k_ties_and_all(impl):
    """On exact index scores with many ties: ``thr``/``cut`` of the kernel
    and of the array form are equal bit for bit, and the rule they give is
    the selection of the equations."""
    _, _, _, qi, ki, w = _op_inputs(0, integer=True)
    got = SA.select_keys(qi, ki, w, 24, impl=impl, interpret=True)
    want = SA.select_keys(qi, ki, w, 24, impl="xla")
    np.testing.assert_array_equal(got.thr, want.thr)
    np.testing.assert_array_equal(got.cut, want.cut)
    np.testing.assert_array_equal(got.counts, want.counts)
    index, sel = _numpy_selection(qi, ki, w, 24)
    t, s = np.arange(256)[:, None], np.arange(256)[None, :]
    key = np.asarray(SA.order_key(jnp.asarray(index, jnp.float32)))
    rule = np.asarray(SA._rule(key, np.asarray(got.thr)[0][:, None],
                               np.asarray(got.cut)[0][:, None], s, t))
    np.testing.assert_array_equal(rule, sel)
    assert int((np.asarray(got.cut) < SA._ALL).sum()) > 10   # ties taken
    assert int(got.counts[0]) == sel.sum() == sum(min(i + 1, 24)
                                                   for i in range(256))
    assert int(got.counts[2]) == SA.blocks_causal(256) == 3


def test_sparse_attention_is_a_float64_softmax_minus_inf_off_the_selection():
    q, k, v, qi, ki, w = _op_inputs(2)
    sel = SA.select_keys(qi, ki, w, 32, impl="xla")
    with jax.default_matmul_precision("highest"):
        o, kl = SA.sparse_attention(q, k, v, qi, ki, w, sel, impl="xla")
    index, mask = _numpy_selection(qi, ki, w, 32)
    q64, k64, v64 = (np.asarray(a, np.float64)[0] for a in (q, k, v))
    heads, pbar = [], 0.0
    for h in range(4):
        s = q64[:, h] @ k64[:, h // 2].T / 4.0
        s = np.where(mask, s, -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        a /= a.sum(-1, keepdims=True)
        pbar = pbar + a / 4
        heads.append(a @ v64[:, h // 2])
    np.testing.assert_allclose(np.asarray(o)[0], np.stack(heads, 1),
                               atol=1e-5)
    mi = np.where(mask, index, -np.inf)
    logq = mi - (mi.max(-1, keepdims=True) + np.log(np.exp(
        mi - mi.max(-1, keepdims=True)).sum(-1, keepdims=True)))
    want = np.sum(np.where(mask, pbar * (np.log(np.where(mask, pbar, 1.0))
                                         - np.where(mask, logq, 0.0)), 0.0),
                  -1)
    np.testing.assert_allclose(np.asarray(kl)[0], want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("l", [200, 256])
def test_the_references_bands_change_no_number(variables, monkeypatch, l):
    """The reference's blocks read the keys up to their band's last query
    (``BANDS`` bands; 200 pads its last block): against one band of every
    key, the keys picked select the same pairs, and the layer's output, its
    KL and their gradients are the same to float32's rounding."""
    p = variables["params"]["layers_0"]
    x, wt = (jax.random.normal(jax.random.PRNGKey(s), (l, 64))
             for s in (9, 10))

    def run(bands):
        monkeypatch.setattr(R, "BANDS", bands)
        picked = R._picked(p, x, SPEC)
        sel = np.zeros((l, l), bool)
        t0 = 0
        for pick in picked:
            for row in np.asarray(pick).reshape(-1, pick.shape[-1]):
                if t0 < l:
                    sel[t0, row[row <= t0]] = True
                t0 += 1
        with jax.default_matmul_precision("highest"):
            out, grads = jax.value_and_grad(
                lambda p_, x_: (lambda y, kl: jnp.sum(y * wt) + kl)(
                    *R.layer_forward(p_, x_, SPEC)), (0, 1))(p, x)
        return sel, out, grads
    banded = run(8)
    assert len(R._bands(l, 64)) == 4
    whole = run(1)
    np.testing.assert_array_equal(banded[0], whole[0])
    assert banded[0].sum() == sum(min(t + 1, SPEC["topk"]) for t in range(l))
    np.testing.assert_allclose(banded[1], whole[1], rtol=1e-6)
    assert _worst(banded[2], whole[2]) < 1e-5


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_with_topk_past_the_row_the_layer_is_full_causal_attention(impl):
    q, k, v, qi, ki, w = _op_inputs(3, l=128)
    sel = SA.select_keys(qi, ki, w, 1 << 20, impl=impl, interpret=True)
    o, _ = SA.sparse_attention(q, k, v, qi, ki, w, sel, impl=impl,
                               interpret=True)
    kr, vr = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bthd,bshd->bhts", q, kr) / 4.0
        s = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), s, -jnp.inf)
        want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), vr)
    assert _rel(o, want) < 1e-5
    assert int(sel.counts[0]) == 128 * 129 // 2


def test_the_kernels_interpreted_equal_the_array_form():
    """Forward, the indexer's loss and every gradient of the kernels under
    the interpreter against the array form, on a selection both see alike
    (exact index scores), two query tiles and two key tiles of a row."""
    q, k, v, _, _, _ = _op_inputs(4, l=256)
    _, _, _, qi, ki, w = _op_inputs(5, l=256, integer=True)

    def run(impl):
        sel = SA.select_keys(qi, ki, w, 48, impl=impl, interpret=True)

        def loss(*a):
            o, kl = SA.sparse_attention(*a, sel, impl=impl, interpret=True)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(
                kl * jnp.arange(kl.shape[1])) / 100, (o, kl)
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, argnums=range(6), has_aux=True)(
                q, k, v, qi, ki, w)
    (_, (oa, kla)), ga = run("xla")
    (_, (op, klp)), gp = run("pallas")
    assert _rel(op, oa) < 1e-5 and _rel(klp, kla) < 1e-5
    for name, a, b in zip(("q", "k", "v", "qi", "ki", "w"), gp, ga):
        assert _rel(a, b) < 1e-5, name


def _unpacked(bits):
    """The kernels' packed selection (B, L / bk, bk / 32, L) as a (B, T, S)
    bool array: bit ``b`` of word row ``r`` of key tile ``j`` is key ``j
    bk + b bk / 32 + r``."""
    bits = np.asarray(bits)
    b, nk, rows, l = bits.shape
    one = (bits[:, :, None] >> np.arange(32)[None, None, :, None, None]) & 1
    return np.swapaxes(one.reshape(b, nk * 32 * rows, l), 1, 2) != 0


def _planted(l, zeros):
    """Exact index inputs of a row of ``l`` and a top-k of ``l / 8``; with
    ``zeros`` a quarter of the keys score exactly 0 against every query and
    no score is negative, so that ties at 0 fill selections up to the
    cut."""
    _, _, _, qi, ki, w = _op_inputs(l, l=l, integer=True)
    if zeros:
        w = jnp.abs(w)
        ki = jnp.where((jnp.arange(l) % 4 == 1)[None, :, None], 0.0, ki)
    return qi, ki, w, l // 8


@functools.lru_cache(maxsize=None)
def _kernel_selection(l, zeros):
    qi, ki, w, topk = _planted(l, zeros)
    return SA.select_keys(qi, ki, w, topk, impl="pallas", interpret=True)


@pytest.mark.parametrize("l,zeros", [(256, False), (1024, True),
                                     (1536, False)])
def test_the_selection_kernel_hands_over_the_array_forms_selection(l, zeros):
    """The selection kernel's bits unpack to the array form's selection
    exactly, count the census's selected pairs and are zero past the
    diagonal; its ``ilse`` is the log-sum-exp of the array form's selected
    scores to float32's rounding.  Exact index scores, in one row with
    ties at an exact 0 cut (:func:`_planted`)."""
    qi, ki, w, topk = _planted(l, zeros)
    got = _kernel_selection(l, zeros)
    want = SA._select_array(qi, ki, w, topk)
    np.testing.assert_array_equal(got.thr, want.thr)
    np.testing.assert_array_equal(got.cut, want.cut)
    index = SA._index_array(qi, ki, w)
    t, s = SA._causal(l)
    sel = np.asarray(SA._rule(SA.order_key(index), want.thr[..., None],
                              want.cut[..., None], s, t))
    np.testing.assert_array_equal(_unpacked(got.bits), sel)
    popcount = np.unpackbits(np.asarray(got.bits).view(np.uint8)).sum()
    assert popcount == int(got.counts[0]) == sel.sum()
    if zeros:
        cut_at_zero = (np.asarray(got.thr) == 0) & (np.asarray(got.cut)
                                                    < SA._ALL)
        assert cut_at_zero.sum() > 10
    bk = min(SA.BLOCK_K, l)
    for i in range(l // SA.BLOCK_Q):
        past = np.asarray(got.bits)[:, SA._last_k(i, SA.BLOCK_Q, bk) + 1:, :,
                                    i * SA.BLOCK_Q:(i + 1) * SA.BLOCK_Q]
        assert not past.any()
    lse = jax.nn.logsumexp(jnp.where(sel, index, -jnp.inf), axis=-1)
    np.testing.assert_allclose(got.ilse, lse, rtol=2e-7, atol=0)


def test_the_forward_kernels_never_read_the_words_past_the_diagonal():
    """Words of the cells past the diagonal set to all ones: the forward's
    ``o`` and ``lse`` and the loss kernel's ``kl`` keep their bits."""
    l = 1024
    q, k, v, _, _, _ = _op_inputs(8, l=l)
    qi, ki, w, _ = _planted(l, True)
    sel = _kernel_selection(l, True)
    past = np.zeros(sel.bits.shape, bool)
    for i in range(l // SA.BLOCK_Q):
        past[:, SA._last_k(i, SA.BLOCK_Q, SA.BLOCK_K) + 1:, :,
             i * SA.BLOCK_Q:(i + 1) * SA.BLOCK_Q] = True
    assert past.sum() == 4 * 16 * 128         # query tiles 0-3, key tile 1
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    ops = SA._index_layout(qi, ki, w, q.dtype)

    def run(bits):
        o, lse = SA._fwd_pallas(qt, kt, vt, bits, 0.25, True)
        kl = SA._kl_pallas(qt, kt, *ops, bits, lse, sel.ilse[:, None], 0.25,
                           True)
        return o, lse, kl
    for a, b in zip(run(sel.bits), run(jnp.where(past, -1, sel.bits))):
        np.testing.assert_array_equal(a, b)


def test_the_backward_carries_its_query_sums_across_key_chunks(monkeypatch):
    """The one backward kernel over a row its key chunks split in three (a
    budget cut so that the tiny heads' chunk is 1,024 keys, two key tiles):
    every gradient against the array form on exact index scores, ties and
    the tie cut included.  Query tiles wholly
    before a chunk write nothing back, and each tile's dQ, dQI and dW carry
    the earlier chunks' sums, under the interpreter that gives the aliased
    input and output one buffer and leaves an output block unset until the
    kernel writes it, as the chip does: a stale or unset block written back
    moves a gradient."""
    l, chunk = 3072, 1024
    per_key = 4 * (2 * 2 * 16 + SA._LANES)
    monkeypatch.setattr(SA, "_CHUNK_BYTES", chunk * per_key)
    assert SA._chunk(l, SA.BLOCK_K, 2, 16, SA._LANES) == chunk == l // 3
    q, k, v, _, _, _ = _op_inputs(6, l=l)
    _, _, _, qi, ki, w = _op_inputs(7, l=l, integer=True)
    sel = SA.select_keys(qi, ki, w, 40, impl="xla")
    assert int((sel.cut < SA._ALL).sum()) > 0          # a tie cut taken

    def grads(impl):
        sel = SA.select_keys(qi, ki, w, 40, impl=impl, interpret=True)

        def loss(*a):
            o, kl = SA.sparse_attention(*a, sel, impl=impl, interpret=True)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(
                kl * jnp.arange(l)) / 100
        with jax.default_matmul_precision("highest"):
            return jax.grad(loss, argnums=range(6))(q, k, v, qi, ki, w)
    for name, a, b in zip(("q", "k", "v", "qi", "ki", "w"), grads("pallas"),
                          grads("xla")):
        assert _rel(a, b) < 1e-5, name


@pytest.mark.parametrize("l", [4096, 32768])
def test_the_backward_chunk_divides_the_row_and_fits_its_budget(l):
    """At the cell's widths (4 key heads of 128, the indexer's key on 128
    lanes) the key chunk is whole key tiles that divide the row, and its
    float32 dK, dV and dKI, held once, fit the launch's budget."""
    chunk = SA._chunk(l, SA.BLOCK_K, 4, 128, SA._LANES)
    assert l % chunk == 0 and chunk % SA.BLOCK_K == 0
    assert chunk == 4096
    assert chunk * 4 * (2 * 4 * 128 + SA._LANES) <= SA._CHUNK_BYTES \
        <= SA._VMEM_LIMIT // 2


def test_the_census_counts_three_kernel_grids_a_layer(monkeypatch):
    """A layer's step at 32,768 visits 8,320 causal cells on each of the
    forward's, the loss's and the backward's grids, and 256 query tiles of
    the selection; the cut's four layers 100,864 a row, and every layer's
    forward reads the selection kernel's bits."""
    assert SA.train_cells(32768) == 3 * 8320 + 256 == 25216
    monkeypatch.setattr(SA, "sparse_impl", functools.partial(
        SA.sparse_impl, backend="tpu"))
    model = create_model("keye_vl2_30b_a3b_4l")
    assert model.attn_tiles_visited(32768) == 4 * 25216 == 100864
    # every layer's forward reads the selection kernel's bits; none under
    # the array form
    assert model.dsa_fwd_bits_layers(32768) == 4
    assert create_model("keye_vl2_30b_a3b_4l", attn_impl="full") \
        .dsa_fwd_bits_layers(32768) == 0


# ---- the holds: each loss trains its own parameters ------------------------

@pytest.mark.parametrize("dsa", ["xla", "pallas"])
def test_each_loss_trains_only_its_own_parameters(variables, dsa):
    """Under the next-token loss alone the indexer's gradient is exactly
    zero; under the indexer's loss alone every other gradient is."""
    ids, tg = _ids(1, 128)
    m = create_model("keye_vl2_tiny", attn_impl="full", dsa_impl=dsa,
                     moe_impl="xla")
    params = variables["params"]

    def losses(p):
        (lm, _), mut = m.apply({"params": p}, ids, tg, mutable=["aux_loss"],
                               method="sequence_loss")
        return lm, sum(jax.tree.leaves(mut["aux_loss"]))
    g_lm = jax.grad(lambda p: losses(p)[0])(params)
    g_kl = jax.grad(lambda p: losses(p)[1])(params)
    assert float(losses(params)[1]) > 0
    for path, g in jax.tree_util.tree_flatten_with_path(g_lm)[0]:
        if any(n in jax.tree_util.keystr(path) for n in INDEXER):
            assert float(jnp.max(jnp.abs(g))) == 0.0, path
    moved = 0
    for path, g in jax.tree_util.tree_flatten_with_path(g_kl)[0]:
        name = jax.tree_util.keystr(path)
        if any(n in name for n in INDEXER):
            moved += float(jnp.max(jnp.abs(g))) > 0
        else:
            assert float(jnp.max(jnp.abs(g))) == 0.0, name
    assert moved == 4 * 5          # every indexer leaf of every layer


# ---- the share of a 128-expert layer ---------------------------------------

def test_eight_shares_of_a_128_expert_layer_add_up_to_the_uncut_layer():
    """A tiny layer of 128 softmax-routed experts, top-8: the program's
    layer holding experts (16 i, 16) for i = 0..7, with what every chip
    computes alike (the attention, the residual) counted once, adds up to
    the uncut reference layer: sum_i y_i - 7 base, where base is the layer
    whose routed experts give nothing."""
    spec = dict(SPEC, experts=128, held=(0, 128), top_k=8)
    shapes = R._layer_shapes(spec)
    full = W.make_variables(11, {"layers_1": shapes}, {}, leaf=R.init_leaf)
    p = full["params"]["layers_1"]
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 64))

    def share(first, zero=False):
        q = dict(p, experts_w13=p["experts_w13"][first:first + 16],
                 experts_w2=p["experts_w2"][first:first + 16] * (not zero))
        return _layer(n_experts=128, top_k=8, held=(first, 16),
                      attn_impl="full", moe_impl="xla").apply(
            {"params": q}, x[None])[0]

    with jax.default_matmul_precision("highest"):
        parts = sum(share(16 * i) for i in range(8)) - 7 * share(0, True)
        want = R.layer_forward(p, x, spec)[0]
    assert _rel(parts, want) < 2e-5
    # and one share alone is not the layer
    assert _rel(share(0), want) > 1e-2


# ---- the whole model ---------------------------------------------------------

def _model_pair(variables, model, ids, tg):
    params = variables["params"]

    def total(p):
        (lm, acc), mut = model.apply({"params": p}, ids, tg,
                                     mutable=["aux_loss"],
                                     method="sequence_loss")
        return lm + sum(jax.tree.leaves(mut["aux_loss"])), lm
    with jax.default_matmul_precision("highest"):
        (_, lm), g = jax.value_and_grad(total, has_aux=True)(params)
    rl, rg, _, _ = R.loss_and_grads(params, {}, ids, tg, SPEC)
    return lm, g, rl, rg


@pytest.mark.parametrize("attn,remat", [("full", "none"), ("full", "full")])
def test_model_loss_and_gradients_match_the_reference(variables, attn,
                                                      remat):
    ids, tg = _ids()
    m = create_model("keye_vl2_tiny", attn_impl=attn, moe_impl="xla",
                     remat_policy=remat)
    with jax.default_matmul_precision("highest"):
        logits = m.apply(variables, ids)
    ref = R.inference_forward(variables["params"], {}, ids, SPEC)
    assert logits.shape == (2, 40, 512) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, ref, atol=2e-5)
    lm, g, rl, rg = _model_pair(variables, m, ids, tg)
    assert abs(float(lm) - float(rl)) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree.leaves(rg)):
        assert _rel(a, b) < 1e-3, (jax.tree_util.keystr(path), _rel(a, b))


@pytest.mark.parametrize("fault", FAULTS.MODEL_FAULTS)
def test_each_planted_fault_moves_the_compared_numbers(variables, fault):
    """A gradient leaf of the faulty program leaves the reference by far
    more than the sound program's 1e-3."""
    ids, tg = _ids()
    m = FAULTS.faulty_model(create_model("keye_vl2_tiny", attn_impl="full",
                                         moe_impl="xla"), fault)
    _, g, _, rg = _model_pair(variables, m, ids, tg)
    assert _worst(g, rg) > 0.02, fault
    # the files are as they were once the faulty trace is done
    assert K.indexer_input is not None and SA._relu(-1.0) == 0.0
    assert FAULTS.faulty_model(m, None) is m


def test_named_scopes_survive_into_the_lowered_program(variables):
    m = create_model("keye_vl2_tiny", attn_impl="full")
    ids, tg = _ids(1, 24)

    def loss(p):
        (lm, _), mut = m.apply({"params": p}, ids, tg, mutable=["aux_loss"],
                               method="sequence_loss")
        return lm + sum(jax.tree.leaves(mut["aux_loss"]))
    text = jax.jit(jax.grad(loss)).lower(
        variables["params"]).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("/embed/", "layers_0/.*attn_proj", "layers_0/.*dsa_index",
                  "layers_1/.*dsa_select", "layers_1/.*attn_sparse",
                  "layers_2/.*dsa_kl", "layers_1/.*moe_router",
                  "layers_2/.*moe_dispatch", "layers_3/.*moe_experts",
                  "layers_3/.*moe_combine", "lm_head_loss"):
        assert any(re.search(scope, n) for n in names), scope
    # the cell's trace_groups file every scope under its own name
    groups = CELL["trace_groups"]
    first = lambda n: next((g for g, pat in groups            # noqa: E731
                            if re.search(pat, n)), None)
    found = {first(n) for n in names}
    assert {"attn_proj", "dsa_index", "dsa_select", "attn_sparse", "dsa_kl",
            "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
            "lm_head_loss", "embed"} <= found
    assert [g for g, _ in groups][-2:] == ["layers_other", "optimizer"]


# ---- three optimizer steps through the one train step ----------------------

@pytest.mark.parametrize("grad_accum", [1, 2], ids=["whole", "accumulated"])
def test_three_adamw_steps_match_the_reference(variables, grad_accum):
    from deepfake_detection_tpu.config import TrainConfig
    from deepfake_detection_tpu.optim import create_optimizer
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_train_step)
    cfg = TrainConfig.from_args(
        ["--model", "keye_vl2_tiny", "--model-version", "",
         "--dataset", "synthetic-tokens", "--seq-len", "40", "-b", "2",
         "--grad-accum", "2", "--opt", "adamw", "--opt-beta2", "0.95",
         "--lr", "1e-3", "--weight-decay", "1e-4", "--clip-grad", "1.0",
         "--compute-dtype", "float32", "--attn-impl", "full"])
    model = create_model("keye_vl2_tiny", attn_impl="full",
                         remat_policy="full")
    tx = create_optimizer(cfg, learning_rate=cfg.lr)
    p0 = jax.tree.map(np.asarray, variables["params"])
    state = create_train_state(
        jax.tree.map(jnp.asarray, {"params": p0, "batch_stats": {}}), tx)
    step = make_train_step(model, tx, clip_grad=cfg.clip_grad,
                           grad_accum=grad_accum)
    kw = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
              clip=1.0)
    rp = jax.tree.map(jnp.asarray, p0)
    ropt = O.init(rp)
    rng = jax.random.PRNGKey(0)
    for i in range(3):
        ids, tg = _ids(rows=4, seed=10 + i)
        with jax.default_matmul_precision("highest"):
            state, metrics = step(state, ids, tg, rng)
        loss, grads, _, _ = R.loss_and_grads(rp, {}, ids, tg, SPEC)
        rp, ropt, g = O.update(rp, grads, ropt, **kw)
        assert abs(float(metrics["loss"]) - float(loss)) < 2e-5 * (i + 1)
        # four rows of 40 tokens through four layers: every causal pair of
        # the first 16 positions, 16 a position after them
        counts = np.asarray(metrics["dsa_counts"])
        assert counts[0] == 4 * 4 * (16 * 17 // 2 + 24 * 16)
        assert counts[1] == counts[2] == 4 * 4 * 1
        assert float(metrics["aux_loss"]) > 0
        if i == 0:
            g1 = O.program_first_gradient(state.opt_state, **kw)
            for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g)):
                assert _rel(jnp.asarray(a), b) < 1e-3
    for (path, a), b, z in zip(
            jax.tree_util.tree_flatten_with_path(state.params)[0],
            jax.tree.leaves(rp), jax.tree.leaves(p0)):
        assert _rel(a - z, b - z) < 2e-2, jax.tree_util.keystr(path)


# ---- the normal runner ------------------------------------------------------

def test_runner_trains_and_logs_the_sparse_attention_census(tmp_path):
    from deepfake_detection_tpu.runners.train import launch_main
    out = launch_main([
        "--model", "keye_vl2_tiny", "--model-version", "",
        "--dataset", "synthetic-tokens", "--seq-len", "32", "-b", "1",
        "--grad-accum", "2", "--opt", "adamw", "--lr", "1e-3",
        "--weight-decay", "1e-4", "--sched", "step", "--decay-rate", "1.0",
        "--epochs", "1", "--clip-grad", "1.0", "--checkpoint-policy", "full",
        "--attn-impl", "full", "--compute-dtype", "float32", "--workers", "1",
        "--log-interval", "4", "--recovery-interval", "0",
        "--output", str(tmp_path)])
    assert np.isfinite(out["loss"])
    run = tmp_path / os.listdir(tmp_path)[0]
    events = [json.loads(line) for line in open(run / "telemetry.jsonl")]
    start = next(e for e in events if e.get("event") == "run_start")
    assert start["dsa_layers"] == 4
    assert start["dsa_fwd_bits_layers"] == 0      # the array form runs
    records = [e for e in events if "counters" in e]
    last = records[-1]["counters"]
    assert last["dsa_selected_pairs_total"] > 0
    assert last["dsa_blocks_touched_total"] == \
        last["dsa_blocks_causal_total"] > 0
    # the drain hands the census and the loss to the telemetry as above
    from deepfake_detection_tpu.obs import TrainTelemetry
    t = TrainTelemetry(dsa_layers=4, dsa_fwd_bits_layers=4)
    t.on_sparse_attention(10, 2, 3, kl_loss=0.5)
    snap = t.snapshot()
    assert (snap["counters"]["dsa_selected_pairs_total"],
            snap["counters"]["dsa_blocks_touched_total"],
            snap["counters"]["dsa_blocks_causal_total"]) == (10, 2, 3)
    assert snap["gauges"]["dsa_kl_loss"] == 0.5
    assert snap["gauges"]["dsa_layers"] == 4
    assert snap["gauges"]["dsa_fwd_bits_layers"] == 4
    assert "dfd_train_dsa_kl_loss" in t.render_prometheus()
