"""Learned sparse attention (DeepSeek-style indexer, top-k keys a query),
with the indexer's KL loss, forward and backward.

For a row of ``L`` positions, query heads ``q`` (H of D), grouped key and
value heads ``k``, ``v`` (Hk of D), and the indexer's queries ``qi`` (J
heads of E), its one key head ``ki`` (E) and its head weights ``w`` (J):

* index scores ``I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s])`` over the
  causal pairs ``s <= t`` (an exact zero is +0);
* the selection ``S_t``: the ``topk`` keys ``s <= t`` of largest ``I[t, s]``,
  ties to the lower ``s`` (``lax.top_k``'s rule), every ``s <= t`` where ``t
  < topk``.  One ``S_t`` serves every head;
* the attention ``o[t, h] = softmax_{s in S_t}(q[t, h] . k[s, g(h)] * scale)
  . v[s, g(h)]``, ``g(h) = h // (H / Hk)``;
* the indexer's loss of a row ``kl[t] = KL(p_t || softmax_{s in S_t}
  I[t, s])`` with ``p_t = sg(mean_h a[t, h, .])`` the heads' mean attention
  weights over ``S_t``.

The selection is a constant to every gradient; ``o`` differentiates to
``q, k, v`` and ``kl`` to ``qi, ki, w`` only (``p`` is held).

**The selection as a threshold.**  A score's ``order_key`` (its float32 bits
as an int32 that orders like the float) turns the selection into a rule
over two int32 a query: ``S_t = {s <= t : key > thr[t] or (key == thr[t]
and s <= cut[t])}``, ``thr`` the ``topk``-th largest key of the row and
``cut`` the index where the ties that fill the ``topk`` end (``2**30``
where every tie is taken; ``thr`` the least int32 where ``t < topk``).  No
``L x L`` array of scores exists anywhere.  The selection kernel hands the
forward kernels the rule applied, one bit a pair (``Selection.bits``: 128
MiB a row of 32,768), and the selected scores' log-sum-exp; the backward
makes the scores of its tile again from ``qi``, ``ki`` and ``w`` with the
one function :func:`_index_t`, at the one tile shape, so it sees the bits
the selection saw.  A score's terms are products float32 holds exactly
(:func:`_term`: each factor split into its bfloat16 part and the rest),
so a compiler that fuses a multiply and an add cannot move a bit of it
from one kernel to the next, and the weighting keeps float32's precision
whatever the operands' dtype.

**Layout and tiles (the TPU kernels).**  Every kernel works on the
transposed tile, **keys on sublanes and queries on lanes**: a cell of
``BLOCK_K`` = 512 keys by ``BLOCK_Q`` = 128 queries, so a query's
statistics (``thr``, ``cut``, a head's ``lse``, the indexer's ``lse``,
``kl``) are lane-dense rows ``(1, 128)`` stored as ``(B, ·, L)``, and a
cell's selection is ``(16, 128)`` int32 words, bit ``b`` of word row ``r``
the key ``16 b + r`` of the cell (:func:`_pack`), so that a bit unpacks to
a whole slab of sublanes.  Inputs ``q``, ``do``: ``(B, H, L, D)``; ``k``,
``v``: ``(B, Hk, L, D)``; ``qi``: ``(B, J, L, 128)`` and ``ki``: ``(B, L,
128)`` (E zero-padded to the lane width); ``w``: ``(B, J, L)`` float32.
Outputs made per query come out transposed, ``(B, H, D, L)`` (``o``,
``dq``) and ``(B, J, 128, L)`` (``dqi``), and are turned back outside.  A
cell runs all H heads against the one mask.

* ``_select_kernel``, grid ``(B, L / 128)``, reads ``qi``, ``ki``, ``w``:
  the query tile's scores against every causal key into a VMEM row ``(L,
  128)`` of keys (16 MiB at 32,768), then 32 passes of bisection over the
  key bits for ``thr``, one for the ties, 16 over the index for ``cut``,
  and one that applies the rule to each causal key tile: it writes the
  tile's bits (``(B, L / 512, 16, L)``, zeros past the diagonal), counts
  the selected pairs and the (128 query, 128 key) blocks holding one, and
  runs the online log-sum-exp of the selected scores (``ilse``).  Writes
  ``thr``, ``cut``, the counts, ``ilse`` and the bits.
* ``_fwd_kernel``, grid ``(B, L / 128, L / 512)``, reads ``q``, ``k``,
  ``v`` and the cell's words: online softmax of every head over the
  selected keys (scratch ``(H, D, 128)`` float32).  Writes ``o`` and each
  head's ``lse``.
* ``_kl_kernel``, same grid, reads ``q``, ``k``, ``qi``, ``ki``, ``w``, the
  cell's words, ``lse`` and ``ilse``: ``p`` from the finished ``lse`` of
  every head, the index scores for ``log q``.  Writes ``kl`` a query.
* ``_bwd_kernel``, grid ``(B, L / C, L / 128, C / 512)`` over key chunks
  of ``C`` keys (:func:`_chunk`: 4,096 at 32,768), reads the operands,
  ``thr``, ``cut``, ``do``, ``lse``, ``delta``, ``ilse`` and the loss's
  cotangent: the whole backward, each cell's mask, ``p``, ``dp`` and ``ds
  = p (dp - delta)`` (FlashAttention-2) and ``dI = g_kl (softmax(I) -
  p)`` made once for dQ, dK, dV and the indexer's three gradients, the
  index heads' ``relu(x)`` kept from the mask's pass in a VMEM scratch
  ``(J, 512, 128)``.  The chunk's dK, dV and dKI stay in VMEM across the
  query tiles; dQ, dQI and dW of a query tile are carried from chunk to
  chunk through HBM, outputs aliased to zeroed inputs.

Cells wholly above the diagonal are skipped and their index maps clamped,
so nothing is copied for them.  Off the TPU the op takes the array form
(:func:`_attend_array`, autodiff of the same expressions; the selection by
``lax.top_k``), which the CPU tests hold the interpreted kernels to.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret

__all__ = ["BLOCK_K", "BLOCK_Q", "SPARSE_RESIDUALS", "Selection",
           "blocks_causal", "order_key", "select_keys", "sparse_attention",
           "sparse_impl", "train_cells"]

# queries a cell takes (on lanes) and keys (on sublanes); the census's
# blocks are (BLOCK_Q, 128)
BLOCK_Q = 128
BLOCK_K = 512
_CENSUS = 128
_LANES = 128
# the selection keeps a (L, 128) int32 row of keys in VMEM: 16 MiB at 32,768
# positions beside the key head's whole row; a v5e core has 128 MiB
_VMEM_LIMIT = 64 * 1024 * 1024
# the backward's key chunk: its float32 dK, dV and dKI, resident in VMEM
# single-buffered beside the tiles' double-buffered blocks
_CHUNK_BYTES = 24 * 1024 * 1024
_INT_MIN = -2 ** 31
# pairs a word of the selection's bits holds
_WORD = 32
# ``cut`` where every tie at ``thr`` is taken
_ALL = 2 ** 30
_NEG_INF = float("-inf")

# the names the op's forward gives what the backward needs of it, and the
# selection's result: a remat policy that saves them runs neither kernel
# again (models/helpers.py:maybe_remat)
SPARSE_RESIDUALS = ("sparse_out", "sparse_lse", "sparse_index_lse",
                    "sparse_thr", "sparse_cut")


class Selection(NamedTuple):
    """``thr``, ``cut`` (B, L) int32: the selection rule's two numbers a
    query; ``counts`` (3,) int32: selected pairs, (128 query, 128 key)
    blocks holding one, causal blocks.  The selection kernel's, for the
    forward kernels (None from the array form): ``ilse`` (B, L) float32,
    the log-sum-exp of a query's selected index scores, and ``bits`` (B,
    L / bk, bk / 32, L) int32, the selection packed (:func:`_pack`; bk =
    ``min(BLOCK_K, L)``), zeros in key tiles past the diagonal."""
    thr: jax.Array
    cut: jax.Array
    counts: jax.Array
    ilse: Optional[jax.Array] = None
    bits: Optional[jax.Array] = None


def order_key(x):
    """float32 -> int32 that orders as the float does (+0 above -0)."""
    b = lax.bitcast_convert_type(x, jnp.int32)
    return b ^ (lax.shift_right_arithmetic(b, 31) & 0x7FFFFFFF)


def _rule(key, thr, cut, s, t):
    """The selection: causal, above the threshold, or at it up to ``cut``."""
    return (s <= t) & ((key > thr) | ((key == thr) & (s <= cut)))


def blocks_causal(l: int) -> int:
    """(128 query, 128 key) blocks of a row of ``l`` holding a causal
    pair."""
    n = -(-l // _CENSUS)
    return n * (n + 1) // 2


def train_cells(l: int) -> int:
    """Grid cells with a causal pair that one train step's kernels visit
    over a row of ``l``: the forward's, the loss's and the backward's (the
    three grids hold the same cells; under remat the saved residuals keep
    the first two from running again) and the selection's query tiles."""
    bq, bk = BLOCK_Q, min(BLOCK_K, l)
    cells = sum(_last_k(i, bq, bk) + 1 for i in range(l // bq))
    return 3 * cells + l // bq


def sparse_impl(l: int, d: int, backend: Optional[str] = None) -> str:
    """The form :func:`sparse_attention` and :func:`select_keys` take with
    ``impl=None``: the kernels on a TPU backend where the row is whole
    tiles and a head fills the lanes, the array form elsewhere."""
    fits = l % BLOCK_K == 0 and d == _LANES
    return "pallas" if fits and \
        (backend or jax.default_backend()) == "tpu" else "xla"


# ---------------------------------------------------------------------------
# the tile every kernel makes again
# ---------------------------------------------------------------------------

def _relu(x):
    """The indexer's activation."""
    return jnp.maximum(x, 0.0)


def _relu_on(x):
    """Where the activation passes its argument (and its gradient)."""
    return x > 0.0


def _relu_scores(ki, qi_j):
    """(bk, bq) float32 ``ki . qi_j`` of one indexer head."""
    return lax.dot_general(ki, qi_j, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _rounded(r):
    """``r`` at bfloat16's precision, held in float32."""
    return r.astype(jnp.bfloat16).astype(jnp.float32)


def _term(w, r):
    """One head's term ``w * relu(x)`` of an index score, made of products
    that float32 holds exactly, so that a fused multiply-add gives the bits
    a multiply then an add gives and every kernel makes the same score
    whatever its compiler contracts: each factor is split into its bfloat16
    part and the rest, and the three products that matter are summed (the
    fourth is below float32's rounding of the whole)."""
    rh, wh = _rounded(r), _rounded(w)
    return wh * rh + (wh * (r - rh) + (w - wh) * rh)


def _index_t(ki, qi_ref, w, keep=None):
    """The index scores of a cell, keys by queries (bk, bq) float32: head by
    head in order, an exact zero made +0.  ``ki`` (bk, E), ``qi_ref[0, j]``
    (bq, E), ``w`` (J, bq); ``keep[j]``, where given, takes head ``j``'s
    ``relu(qi_j . ki)``."""
    acc = None
    for j in range(w.shape[0]):
        r = _relu(_relu_scores(ki, qi_ref[0, j]))
        if keep is not None:
            keep[j] = r
        t = _term(w[j:j + 1, :], r)
        acc = t if acc is None else acc + t
    return jnp.where(acc == 0.0, 0.0, acc)


def _positions(s0, t0, bk, bq):
    s = s0 + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    t = t0 + lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    return s, t


def _cell_mask(ki_ref, qi_ref, w_ref, thr_ref, cut_ref, s0, t0, keep=None):
    """(index scores, selection) of a cell, both (bk, bq)."""
    w = w_ref[0]
    index = _index_t(ki_ref[0], qi_ref, w, keep)
    bk, bq = index.shape
    s, t = _positions(s0, t0, bk, bq)
    return index, _rule(order_key(index), thr_ref[0], cut_ref[0], s, t)


def _pack(hit):
    """A cell's selection (bk, bq) bool as (bk / 32, bq) int32 words: bit
    ``b`` of word row ``r`` is key ``r + b bk / 32``, so that each bit
    unpacks to a whole slab of sublanes."""
    rows = hit.shape[0] // _WORD
    hit = hit.astype(jnp.int32)
    word = hit[:rows]
    for b in range(1, _WORD):
        word = word | lax.shift_left(hit[b * rows:(b + 1) * rows], b)
    return word


def _unpack(word):
    """:func:`_pack` undone: (bk, bq) bool."""
    return jnp.concatenate([lax.shift_right_logical(word, b) & 1
                            for b in range(_WORD)], axis=0) != 0


def _spec(block, index_map):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


def _params():
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _last_k(i, bq, bk):
    """The last key tile query tile ``i`` sees."""
    return ((i + 1) * bq - 1) // bk


def _first_q(j, bq, bk):
    """The first query tile that sees key tile ``j``."""
    return (j * bk) // bq


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------

def _select_kernel(qi_ref, ki_ref, w_ref, thr_ref, cut_ref, cnt_ref,
                   ilse_ref, bits_ref, buf, *, topk, bk, bits):
    bq = w_ref.shape[-1]
    i = pl.program_id(1)
    t0 = i * bq
    chunks = (t0 + bq + bk - 1) // bk         # key tiles with a causal key
    w = w_ref[0]
    t_row = t0 + lax.broadcasted_iota(jnp.int32, (1, bq), 1)

    def fill(c, carry):
        s0 = pl.multiple_of(c * bk, bk)
        key = order_key(_index_t(ki_ref[0, pl.ds(s0, bk), :], qi_ref, w))
        s, t = _positions(s0, t0, bk, bq)
        buf[pl.ds(s0, bk), :] = jnp.where(s <= t, key, _INT_MIN)
        return carry
    lax.fori_loop(0, chunks, fill, 0)

    def count(pred):
        """(1, bq) float32: over the row's causal keys, how many ``pred``
        (key tile, its first position) holds for."""
        def body(c, acc):
            s0 = pl.multiple_of(c * bk, bk)
            hit = pred(buf[pl.ds(s0, bk), :], s0).astype(jnp.float32)
            return acc + jnp.sum(hit, axis=0, keepdims=True)
        return lax.fori_loop(0, chunks, body,
                             jnp.zeros((1, bq), jnp.float32))

    # thr: the largest key that ``topk`` keys reach, bit by bit from the top
    # in the unsigned order (sign bit flipped)
    def bisect(n, t_u):
        cand_u = t_u | lax.shift_left(jnp.int32(1), 31 - n)
        cand = cand_u ^ _INT_MIN
        reach = count(lambda blk, s0: blk >= cand)
        return jnp.where(reach >= topk, cand_u, t_u)
    thr = lax.fori_loop(0, 32, bisect, jnp.zeros((1, bq), jnp.int32)) \
        ^ _INT_MIN
    few = t_row < topk                   # every causal key is selected
    thr = jnp.where(few, _INT_MIN, thr)
    above = count(lambda blk, s0: blk > thr)
    ties = count(lambda blk, s0: blk == thr)
    need = topk - above

    # cut: the largest c with fewer than ``need`` ties before it
    def index_bisect(n, c):
        cand = c | lax.shift_left(jnp.int32(1), bits - 1 - n)
        before = count(lambda blk, s0: (blk == thr) & (
            s0 + lax.broadcasted_iota(jnp.int32, blk.shape, 0) < cand))
        return jnp.where(before < need, cand, c)
    cut = lax.fori_loop(0, bits, index_bisect,
                        jnp.zeros((1, bq), jnp.int32))
    cut = jnp.where(few | (ties <= need), _ALL, cut)
    thr_ref[0] = thr
    cut_ref[0] = cut

    # the selection of each causal key tile, packed, with the census and
    # the online log-sum-exp of the selected scores (a key is its own
    # score: order_key undoes itself)
    def tally(c, carry):
        touched, picked, m, l_ = carry
        s0 = pl.multiple_of(c * bk, bk)
        s, t = _positions(s0, t0, bk, bq)
        key = buf[pl.ds(s0, bk), :]
        hit = _rule(key, thr, cut, s, t)
        bits_ref[0, c] = _pack(hit)
        index = lax.bitcast_convert_type(order_key(key), jnp.float32)
        _, _, m, l_ = _softmax_step(jnp.where(hit, index, _NEG_INF), m, l_)
        hit = hit.astype(jnp.float32)
        picked = picked + jnp.sum(hit, keepdims=True)
        for b in range(bk // _CENSUS):
            block = hit[b * _CENSUS:(b + 1) * _CENSUS, :]
            touched = touched + jnp.max(block, keepdims=True)
        return touched, picked, m, l_
    zero = jnp.zeros((1, 1), jnp.float32)
    touched, picked, m, l_ = lax.fori_loop(
        0, chunks, tally, (zero, zero, jnp.full((1, bq), _NEG_INF),
                           jnp.zeros((1, bq), jnp.float32)))
    ilse_ref[0] = _lse(m, l_)

    # key tiles past the diagonal: zeros, never read
    def clear(c, carry):
        bits_ref[0, c] = jnp.zeros(bits_ref.shape[2:], jnp.int32)
        return carry
    lax.fori_loop(chunks, bits_ref.shape[1], clear, 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    cnt_ref[0] = jnp.where(lane == 0, touched,
                           jnp.where(lane == 1, picked, 0.0))


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select_pallas(qi, ki, w, topk, interpret):
    """qi (B, J, L, E), ki (B, L, E), w (B, J, L) -> Selection."""
    b, nj, l, e = qi.shape
    bq, bk = BLOCK_Q, min(BLOCK_K, l)
    nq, nk = l // bq, l // bk
    words = bk // _WORD
    thr, cut, cnt, ilse, bits = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, bk=bk,
                          bits=max(1, (l - 1).bit_length())),
        grid=(b, nq),
        in_specs=[_spec((1, nj, bq, e), lambda b_, i: (b_, 0, i, 0)),
                  _spec((1, l, e), lambda b_, i: (b_, 0, 0)),
                  _spec((1, nj, bq), lambda b_, i: (b_, 0, i))],
        out_specs=[_spec((1, 1, bq), lambda b_, i: (b_, 0, i)),
                   _spec((1, 1, bq), lambda b_, i: (b_, 0, i)),
                   _spec((1, 1, _LANES), lambda b_, i: (b_, 0, i)),
                   _spec((1, 1, bq), lambda b_, i: (b_, 0, i)),
                   _spec((1, nk, words, bq), lambda b_, i: (b_, 0, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((b, 1, l), jnp.int32),
                   jax.ShapeDtypeStruct((b, 1, l), jnp.int32),
                   jax.ShapeDtypeStruct((b, 1, nq * _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, l), jnp.float32),
                   jax.ShapeDtypeStruct((b, nk, words, l), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((l, bq), jnp.int32)],
        compiler_params=_params(),
        interpret=interpret,
    )(qi, ki, w)
    cnt = cnt.reshape(b, nq, _LANES)
    counts = jnp.stack([jnp.sum(cnt[..., 1]), jnp.sum(cnt[..., 0]),
                        jnp.float32(b * blocks_causal(l))])
    return Selection(thr[:, 0], cut[:, 0], counts.astype(jnp.int32),
                     ilse[:, 0], bits)


# ---------------------------------------------------------------------------
# forward: the attention over the selection's bits
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bits_ref, o_ref, lse_ref, acc, m_ref,
                l_ref, *, scale):
    nh, bq = q_ref.shape[1], q_ref.shape[2]
    nkv, bk = k_ref.shape[1], k_ref.shape[2]
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j <= _last_k(i, bq, bk))
    def _accumulate():
        sel = _unpack(bits_ref[0, 0])
        for h in range(nh):
            g = h // (nh // nkv)
            s = lax.dot_general(k_ref[0, g], q_ref[0, h],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            p, corr = _online(jnp.where(sel, s, _NEG_INF), m_ref, l_ref, h)
            acc[h] = acc[h] * corr + lax.dot_general(
                v_ref[0, g], p.astype(v_ref.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(j == _last_k(i, bq, bk))
    def _finalize():
        for h in range(nh):
            lh = jnp.maximum(l_ref[h:h + 1, :], 1e-30)
            o_ref[0, h] = (acc[h] / lh).astype(o_ref.dtype)
        lse_ref[0] = _lse(m_ref[...], l_ref[...])


def _safe(m):
    return jnp.where(m == _NEG_INF, 0.0, m)


def _lse(m, l_):
    """A finished online softmax's log-sum-exp."""
    return _safe(m) + jnp.log(jnp.maximum(l_, 1e-30))


def _softmax_step(s, m_prev, l_prev):
    """One key tile of an online softmax over rows ``(1, bq)``: the tile's
    weights (bk, bq), the old sum's correction and the new statistics."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    m_safe = _safe(m_new)
    p = jnp.exp(s - m_safe)
    corr = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
    return p, corr, m_new, l_prev * corr + jnp.sum(p, axis=0, keepdims=True)


def _online(s, m_ref, l_ref, h):
    """:func:`_softmax_step` on row ``h`` of the statistics in VMEM: the
    tile's weights and the old sum's correction; the statistics updated."""
    p, corr, m_new, l_new = _softmax_step(s, m_ref[h:h + 1, :],
                                          l_ref[h:h + 1, :])
    l_ref[h:h + 1, :] = l_new
    m_ref[h:h + 1, :] = m_new
    return p, corr


def _visible(i, j, bq, bk):
    """Key tile ``j`` of query tile ``i``'s row, past the diagonal the last
    visible one: nothing is copied for the cells skipped."""
    return jnp.minimum(j, _last_k(i, bq, bk))


def _q_spec(rows, bq, width):
    return _spec((1, rows, bq, width), lambda b, i, j: (b, 0, i, 0))


def _k_spec(rows, bq, bk, width):
    return _spec((1, rows, bk, width),
                 lambda b, i, j: (b, 0, _visible(i, j, bq, bk), 0))


def _bits_spec(bq, bk):
    return _spec((1, 1, bk // _WORD, bq),
                 lambda b, i, j: (b, _visible(i, j, bq, bk), 0, i))


def _row_spec(rows, bq):
    return _spec((1, rows, bq), lambda b, i, j: (b, 0, i))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _fwd_pallas(q, k, v, bits, scale, interpret):
    b, nh, l, d = q.shape
    nkv = k.shape[1]
    bq, bk = BLOCK_Q, min(BLOCK_K, l)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(b, l // bq, l // bk),
        in_specs=[_q_spec(nh, bq, d), _k_spec(nkv, bq, bk, d),
                  _k_spec(nkv, bq, bk, d), _bits_spec(bq, bk)],
        out_specs=[_spec((1, nh, d, bq), lambda b_, i, j: (b_, 0, 0, i)),
                   _row_spec(nh, bq)],
        out_shape=[jax.ShapeDtypeStruct((b, nh, d, l), q.dtype),
                   jax.ShapeDtypeStruct((b, nh, l), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((nh, d, bq), jnp.float32),
                        pltpu.VMEM((nh, bq), jnp.float32),
                        pltpu.VMEM((nh, bq), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, v, bits)


# ---------------------------------------------------------------------------
# the indexer's loss
# ---------------------------------------------------------------------------

def _heads_mean_p(q_ref, k_ref, lse_ref, sel, scale, extra=None):
    """``p`` (bk, bq): the heads' mean attention weights of the cell, 0
    off the selection.  ``extra(h, g, p_h)`` runs on each head's weights."""
    nh, nkv = q_ref.shape[1], k_ref.shape[1]
    total = None
    for h in range(nh):
        g = h // (nh // nkv)
        s = lax.dot_general(k_ref[0, g], q_ref[0, h],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        p = jnp.where(sel, jnp.exp(s - lse_ref[0, h:h + 1, :]), 0.0)
        if extra is not None:
            extra(h, g, p)
        total = p if total is None else total + p
    return total * (1.0 / nh)


def _kl_kernel(q_ref, k_ref, qi_ref, ki_ref, w_ref, bits_ref, lse_ref,
               ilse_ref, kl_ref, kacc, *, scale):
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        kacc[...] = jnp.zeros_like(kacc)

    @pl.when(j <= _last_k(i, bq, bk))
    def _accumulate():
        sel = _unpack(bits_ref[0, 0])
        index = _index_t(ki_ref[0], qi_ref, w_ref[0])
        p = _heads_mean_p(q_ref, k_ref, lse_ref, sel, scale)
        logq = index - ilse_ref[0]
        term = jnp.where(p > 0.0, p * jnp.log(jnp.maximum(p, 1e-38)), 0.0) \
            - p * jnp.where(sel, logq, 0.0)
        kacc[...] += jnp.sum(term, axis=0, keepdims=True)

    @pl.when(j == _last_k(i, bq, bk))
    def _finalize():
        kl_ref[0] = kacc[...]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _kl_pallas(q, k, qi, ki, w, bits, lse, ilse, scale, interpret):
    b, nh, l, d = q.shape
    nkv, nj, e = k.shape[1], qi.shape[1], qi.shape[3]
    bq, bk = BLOCK_Q, min(BLOCK_K, l)
    return pl.pallas_call(
        functools.partial(_kl_kernel, scale=scale),
        grid=(b, l // bq, l // bk),
        in_specs=[_q_spec(nh, bq, d), _k_spec(nkv, bq, bk, d),
                  _q_spec(nj, bq, e),
                  _spec((1, bk, e),
                        lambda b_, i, j: (b_, _visible(i, j, bq, bk), 0)),
                  _row_spec(nj, bq), _bits_spec(bq, bk), _row_spec(nh, bq),
                  _row_spec(1, bq)],
        out_specs=_row_spec(1, bq),
        out_shape=jax.ShapeDtypeStruct((b, 1, l), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bq), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, qi, ki, w, bits, lse, ilse)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _index_grad(index, sel, p, ilse, gkl):
    """dI of a cell: ``g_kl (softmax(I) - p)`` on the selection."""
    return jnp.where(sel, gkl * (jnp.exp(index - ilse) - p), 0.0)


def _bwd_kernel(q_ref, k_ref, v_ref, qi_ref, ki_ref, w_ref, thr_ref, cut_ref,
                do_ref, lse_ref, delta_ref, ilse_ref, gkl_ref, dq_in, dqi_in,
                dw_in, dq_ref, dqi_ref, dw_ref, dk_ref, dv_ref, dki_ref,
                relu_x, *, scale, chunk):
    """One (row, key chunk, query tile, key tile of the chunk) cell of the
    whole backward: the cell's mask, ``p``, ``dp`` and ``ds`` of every head
    made once for all six gradients, the index heads' ``relu(x)`` kept in
    ``relu_x`` from the mask's pass.  dK, dV and dKI of the chunk are output
    blocks named by the chunk alone, resident across both inner axes; dQ,
    dQI and dW of the query tile are named by the tile and carry the earlier
    chunks' sums in (``*_in``, aliased to them), so each gradient sums in
    the order the two-kernel form summed it: key tiles ascending for a
    query, query tiles ascending for a key, heads in order."""
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    c, i, jl = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    j = c * (chunk // bk) + jl

    @pl.when((i == 0) & (jl == 0))
    def _init_chunk():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(j <= _last_k(i, bq, bk))
    def _accumulate():
        # the tile's first visible cell of the chunk: the sums so far in
        @pl.when(jl == 0)
        def _carry():
            dq_ref[...] = dq_in[...]
            dqi_ref[...] = dqi_in[...]
            dw_ref[...] = dw_in[...]

        index, sel = _cell_mask(ki_ref, qi_ref, w_ref, thr_ref, cut_ref,
                                j * bk, i * bq, relu_x)
        cd = q_ref.dtype
        rows = pl.ds(pl.multiple_of(jl * bk, bk), bk)

        def head(h, g, p):
            dp = lax.dot_general(v_ref[0, g], do_ref[0, h],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, h:h + 1, :]) * scale).astype(cd)
            dv_ref[0, g, rows] += jnp.dot(p.astype(cd), do_ref[0, h],
                                          preferred_element_type=jnp.float32)
            dk_ref[0, g, rows] += jnp.dot(ds, q_ref[0, h],
                                          preferred_element_type=jnp.float32)
            dq_ref[0, h] += lax.dot_general(
                k_ref[0, g], ds, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        p = _heads_mean_p(q_ref, k_ref, lse_ref, sel, scale, head)
        di = _index_grad(index, sel, p, ilse_ref[0], gkl_ref[0])
        w = w_ref[0]
        ki = ki_ref[0]
        for jj in range(w.shape[0]):
            r = relu_x[jj]
            gj = jnp.where(_relu_on(r), di * w[jj:jj + 1, :], 0.0).astype(
                ki.dtype)
            dki_ref[0, rows] += jnp.dot(gj, qi_ref[0, jj],
                                        preferred_element_type=jnp.float32)
            dqi_ref[0, jj] += lax.dot_general(
                ki, gj, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dw_ref[0, jj:jj + 1, :] += jnp.sum(di * r, axis=0, keepdims=True)


def _chunk(l, bk, nkv, d, e):
    """Keys a key chunk of the backward spans: the most key tiles, a power
    of two dividing ``l``, whose float32 dK, dV and dKI fit
    ``_CHUNK_BYTES``."""
    per_key = 4 * (2 * nkv * d + e)
    c = bk
    while l % (2 * c) == 0 and 2 * c * per_key <= _CHUNK_BYTES:
        c *= 2
    return c


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _bwd_pallas(res, do, gkl, scale, interpret):
    q, k, v, qi, ki, w, thr, cut, o, lse, ilse = res
    b, nh, l, d = q.shape
    nkv, nj, e = k.shape[1], qi.shape[1], qi.shape[3]
    bq, bk = BLOCK_Q, min(BLOCK_K, l)
    chunk = _chunk(l, bk, nkv, d, e)
    nc = chunk // bk
    # o and do in the kernels' (B, H, L, D); delta = rowsum(do * o)
    delta = jnp.sum(do.astype(jnp.float32) * jnp.swapaxes(
        o, 2, 3).astype(jnp.float32), axis=-1)
    gkl = gkl.astype(jnp.float32)[:, None, :]
    dq, dqi, dw = (jnp.zeros((b, nh, d, l), jnp.float32),
                   jnp.zeros((b, nj, e, l), jnp.float32),
                   jnp.zeros((b, nj, l), jnp.float32))

    # (B, key chunk, query tile, key tile of the chunk): query tiles wholly
    # before the chunk name its first visible one, key tiles past the
    # diagonal the last visible one, so nothing is copied or written back
    # for them
    def qt(c, i):
        return jnp.maximum(i, _first_q(c * nc, bq, bk))

    def kt(c, i, jl):
        return jnp.minimum(c * nc + jl, _last_k(qt(c, i), bq, bk))

    def q4(b_, c, i, jl):
        return (b_, 0, qt(c, i), 0)

    def q3(b_, c, i, jl):
        return (b_, 0, qt(c, i))

    def qt4(b_, c, i, jl):
        return (b_, 0, 0, qt(c, i))

    def k4(b_, c, i, jl):
        return (b_, 0, kt(c, i, jl), 0)

    def once(block, index_map):
        # resident across both inner axes: written back once a chunk
        return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM,
                            pipeline_mode=pl.Buffered(1))
    carried = [_spec((1, nh, d, bq), qt4), _spec((1, nj, e, bq), qt4),
               _spec((1, nj, bq), q3)]
    in_specs = [_spec((1, nh, bq, d), q4),                        # q
                _spec((1, nkv, bk, d), k4),                       # k
                _spec((1, nkv, bk, d), k4),                       # v
                _spec((1, nj, bq, e), q4),                        # qi
                _spec((1, bk, e), lambda b_, c, i, jl: (b_, kt(c, i, jl), 0)),
                _spec((1, nj, bq), q3),                           # w
                _spec((1, 1, bq), q3), _spec((1, 1, bq), q3),     # thr, cut
                _spec((1, nh, bq, d), q4),                        # do
                _spec((1, nh, bq), q3), _spec((1, nh, bq), q3),   # lse, delta
                _spec((1, 1, bq), q3), _spec((1, 1, bq), q3)]     # ilse, gkl
    n_in = len(in_specs)
    dq, dqi, dw, dk, dv, dki = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, chunk=chunk),
        grid=(b, l // chunk, l // bq, nc),
        in_specs=in_specs + carried,
        out_specs=carried + [
            once((1, nkv, chunk, d), lambda b_, c, i, jl: (b_, 0, c, 0)),
            once((1, nkv, chunk, d), lambda b_, c, i, jl: (b_, 0, c, 0)),
            once((1, chunk, e), lambda b_, c, i, jl: (b_, c, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32)
                   for x in (dq, dqi, dw, k, v, ki)],
        input_output_aliases={n_in: 0, n_in + 1: 1, n_in + 2: 2},
        scratch_shapes=[pltpu.VMEM((nj, bk, bq), jnp.float32)],
        compiler_params=_params(),
        # the TPU interpreter, whose aliased input and output share one
        # buffer as on the chip (the plain interpreter copies the input)
        interpret=pltpu.InterpretParams() if interpret is True else interpret,
    )(q, k, v, qi, ki, w, thr, cut, do, lse, delta, ilse, gkl, dq, dqi, dw)
    return dq, dk, dv, dqi, dki, dw


# ---------------------------------------------------------------------------
# the array form
# ---------------------------------------------------------------------------

def _index_array(qi, ki, w):
    """(B, T, S) float32 index scores, head by head in order, +0 for 0:
    the kernels' terms (:func:`_term`), differentiated as ``w * relu(x)``
    with relu's derivative 0 at 0, as the kernels take it."""
    w = w.astype(jnp.float32)
    acc = None
    for j in range(qi.shape[2]):
        x = jnp.einsum("bte,bse->bts", qi[:, :, j], ki,
                       preferred_element_type=jnp.float32)
        r = jnp.where(_relu_on(x), x, 0.0)
        wj = w[:, :, j, None]
        plain = wj * r
        t = plain + lax.stop_gradient(_term(wj, r) - plain)
        acc = t if acc is None else acc + t
    # -0 made +0 without cutting the gradient at 0
    return acc + lax.stop_gradient(jnp.where(acc == 0.0, 0.0, acc) - acc)


def _causal(l):
    pos = jnp.arange(l)
    return pos[None, :, None], pos[None, None, :]   # t, s


def _select_array(qi, ki, w, topk):
    """The selection by ``lax.top_k`` over the causal index scores."""
    index = _index_array(qi, ki, w)
    b, l, _ = index.shape
    t, s = _causal(l)
    k = min(topk, l)
    vals, idx = lax.top_k(jnp.where(s <= t, index, _NEG_INF), k)
    keys = order_key(vals)
    thr = keys[..., -1]
    tie = keys == thr[..., None]
    cut = jnp.max(jnp.where(tie, idx, -1), axis=-1)
    row_keys = order_key(index)
    ties = jnp.sum((row_keys == thr[..., None]) & (s <= t), axis=-1)
    few = jnp.arange(l)[None, :] < topk
    excess = ties > jnp.sum(tie, axis=-1)
    thr = jnp.where(few, _INT_MIN, thr)
    cut = jnp.where(few | ~excess, _ALL, cut)
    sel = _rule(row_keys, thr[..., None], cut[..., None], s, t)
    return Selection(thr.astype(jnp.int32), cut.astype(jnp.int32),
                     _census_array(sel))


def _census_array(sel):
    """(selected pairs, touched blocks, causal blocks) of a (B, T, S)
    selection."""
    b, l, _ = sel.shape
    n = -(-l // _CENSUS)
    pad = n * _CENSUS - l
    blk = jnp.pad(sel, ((0, 0), (0, pad), (0, pad))).reshape(
        b, n, _CENSUS, n, _CENSUS).any(axis=(2, 4))
    return jnp.stack([jnp.sum(sel), jnp.sum(blk),
                      jnp.int32(b * blocks_causal(l))]).astype(jnp.int32)


def _attend_array(q, k, v, qi, ki, w, thr, cut, scale):
    """(o (B, L, H, D), kl (B, L)) by autodiff-able array expressions."""
    b, l, nh, d = q.shape
    index = _index_array(qi, ki, w)
    t, s = _causal(l)
    sel = _rule(order_key(index), thr[..., None], cut[..., None], s, t)
    rep = nh // k.shape[2]
    kr, vr = (jnp.repeat(x, rep, axis=2) for x in (k, v))
    sc = jnp.einsum("bthd,bshd->bhts", q, kr,
                    preferred_element_type=jnp.float32) * scale
    a = jax.nn.softmax(jnp.where(sel[:, None], sc, _NEG_INF), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", a.astype(v.dtype), vr,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    with jax.named_scope("dsa_kl"):
        p = lax.stop_gradient(jnp.mean(a, axis=1))
        masked = jnp.where(sel, index, _NEG_INF)
        logq = jnp.where(sel, masked - jax.nn.logsumexp(
            masked, axis=-1, keepdims=True), 0.0)
        plogp = jnp.where(p > 0.0, p * jnp.log(jnp.where(p > 0.0, p, 1.0)),
                          0.0)
        kl = jnp.sum(jnp.where(sel, plogp - p * logq, 0.0), axis=-1)
    return o, kl


# ---------------------------------------------------------------------------
# the ops
# ---------------------------------------------------------------------------

def _pad_lanes(x):
    e = x.shape[-1]
    return x if e == _LANES else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, _LANES - e)])


def _index_layout(qi, ki, w, dtype):
    """The indexer's operands in the kernels' layout and ``dtype``; ``w``
    float32."""
    return (_pad_lanes(jnp.swapaxes(qi, 1, 2)).astype(dtype),
            _pad_lanes(ki).astype(dtype),
            jnp.swapaxes(w.astype(jnp.float32), 1, 2))


def select_keys(qi, ki, w, topk: int, impl: Optional[str] = None,
                interpret: Optional[bool] = None) -> Selection:
    """The selection of every query: ``qi`` (B, L, J, E), ``ki`` (B, L,
    E), ``w`` (B, L, J).  A constant: no gradient passes."""
    qi, ki, w = (lax.stop_gradient(x) for x in (qi, ki, w))
    impl = impl or sparse_impl(qi.shape[1], _LANES)
    if impl == "xla":
        sel = _select_array(qi, ki, w, topk)
    else:
        interpret = resolve_interpret(interpret, "select_keys")
        sel = _select_pallas(*_index_layout(qi, ki, w, qi.dtype), topk,
                             interpret)
        # ilse is saved for the backward; the bits, which only the forward
        # kernels read, are not
        sel = sel._replace(ilse=checkpoint_name(sel.ilse,
                                                SPARSE_RESIDUALS[2]))
    return sel._replace(thr=checkpoint_name(sel.thr, SPARSE_RESIDUALS[3]),
                        cut=checkpoint_name(sel.cut, SPARSE_RESIDUALS[4]))


def sparse_attention(q, k, v, qi, ki, w, sel: Selection,
                     scale: Optional[float] = None,
                     impl: Optional[str] = None,
                     interpret: Optional[bool] = None):
    """``(o (B, L, H, D), kl (B, L) float32)`` over the selection ``sel``
    (:func:`select_keys`, in the same form): ``q`` (B, L, H, D), ``k``,
    ``v`` (B, L, Hk, D), the indexer's ``qi`` (B, L, J, E), ``ki`` (B, L,
    E), ``w`` (B, L, J).  The kernels take the MXU's operands in ``q``'s
    dtype, float32 statistics and accumulators."""
    b, l, nh, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    impl = impl or sparse_impl(l, d)
    if impl == "xla":
        return _attend_array(q, k, v, qi, ki, w, sel.thr, sel.cut, scale)
    if sel.bits is None:
        raise ValueError("the kernels read the selection kernel's bits: "
                         "select_keys in the kernels' form")
    interpret = resolve_interpret(interpret, "sparse_attention")
    @jax.custom_vjp
    def op(qp, kp, vp, qip, kip, wp, thr, cut, ilse, bits):
        return fwd(qp, kp, vp, qip, kip, wp, thr, cut, ilse, bits)[0]

    def fwd(qp, kp, vp, qip, kip, wp, thr, cut, ilse, bits):
        o, lse = _fwd_pallas(qp, kp, vp, bits, scale, interpret)
        o = checkpoint_name(o, SPARSE_RESIDUALS[0])
        lse = checkpoint_name(lse, SPARSE_RESIDUALS[1])
        with jax.named_scope("dsa_kl"):
            kl = _kl_pallas(qp, kp, qip, kip, wp, bits, lse, ilse, scale,
                            interpret)
        return (o, kl), (qp, kp, vp, qip, kip, wp, thr, cut, o, lse, ilse)

    def bwd(res, g):
        do_t, gkl = g
        dq, dk, dv, dqi, dki, dw = _bwd_pallas(
            res, jnp.swapaxes(do_t, 2, 3).astype(res[0].dtype), gkl[:, 0],
            scale, interpret)
        qp, kp, vp, qip, kip, wp = res[:6]
        return (jnp.swapaxes(dq, 2, 3).astype(qp.dtype),
                dk.astype(kp.dtype), dv.astype(vp.dtype),
                jnp.swapaxes(dqi, 2, 3).astype(qip.dtype),
                dki.astype(kip.dtype), dw.astype(wp.dtype), None, None, None,
                None)

    op.defvjp(fwd, bwd)
    # the selection's layout, in which the backward makes its scores again
    o_t, kl = op(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                 jnp.swapaxes(v, 1, 2), *_index_layout(qi, ki, w, qi.dtype),
                 sel.thr[:, None], sel.cut[:, None], sel.ilse[:, None],
                 sel.bits)
    # (B, H, D, L) -> (B, L, H, D)
    return jnp.transpose(o_t, (0, 3, 1, 2)), kl[:, 0]
