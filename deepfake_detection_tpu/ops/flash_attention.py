"""Fused flash attention as Pallas TPU kernels (forward + one fused backward,
custom VJP; the split backward pair for ring attention and long rows).

The reference framework has no attention op at all (its temporal axis is a
channel concat, SURVEY.md §2.7); attention enters this framework through the
ViT families, the sequence models and the sequence-parallel machinery in
``parallel/ring_attention.py``.  XLA's dense softmax-attention materialises
the (L, L) score matrix in HBM — O(L²) memory traffic, which caps sequence
length and wastes HBM bandwidth (the usual TPU bottleneck).  This module
implements the standard blocked online-softmax formulation (FlashAttention-2
schedule) as Pallas kernels so scores never leave VMEM.

Every kernel uses the canonical TPU grid structure: the *tile* axis is the
innermost (sequential) grid dimension, so Pallas pipelines one ``(block, d)``
tile at a time through VMEM while online-softmax / gradient accumulators
live in VMEM scratch that persists across the inner grid steps:

* forward:          grid (B·H, Q blocks, K tiles) — scratch (acc, m, l);
                    emits O and the per-row logsumexp the backward reuses.
                    ``m`` and ``l`` stay lane-replicated ``(rows, 128)``
                    values from the load of their scratch to its store,
                    repeated across a tile where it is wider
                    (:func:`_lanes`), never ``(rows, 1)``; a masked score
                    is -inf before the exponential, so ``p`` needs no
                    second select (PR 35: the two together take a 1024²
                    tile from 5.2 to 4.5 µs on a v5e, PERF.md §6).
* backward, fused:  grid (B·H, K blocks, Q tiles) — scratch (dK, dV) of one
                    key block; dQ of the head's **whole row**, float32, is
                    an output block resident across both inner axes and
                    accumulated in place.  A cell makes ``s``,
                    the mask, ``p``, ``dp`` and the FlashAttention-2
                    ``dS = P ∘ (dP − δ)``, δ = rowsum(dO ∘ O), once and
                    adds all three gradients from them: five matrix
                    products a tile.  Key blocks reach each query tile in
                    ascending order, the order the dQ kernel below adds
                    them in, so the bits are the pair's.
* backward, split:  dK/dV on the same grid, then dQ on the forward's grid
                    (scratch dQ of one block): each makes ``s`` … ``dS``
                    again, seven products a tile.  Ring attention calls the
                    pair by name (its offsets are traced and its dQ sums
                    over ring steps outside), and a row too long for VMEM
                    takes it.

:func:`fused_bwd` chooses between the two from what the op can see: static
offsets and the row's bytes (``_DQ_ROW_BYTES``: 16 MiB of the 64 MiB the
fused launch is given, 32,768 positions of a 128-lane head).  The three
backward kernels share :func:`_tile_grads` for the cell's expression.

**Residuals under remat.**  The op's custom VJP keeps ``(q, k, v, out,
lse)``.  Its forward tags ``out`` (the op's result and its residual both,
one value, in the padded layout) and ``lse`` (one float32 a row, cut from
the kernel's lane-replicated array and widened again in the backward) with
``jax.ad_checkpoint.checkpoint_name`` under :data:`FLASH_RESIDUALS`.  A
remat policy that saves those names (``models/helpers.py:maybe_remat``)
keeps the two from the forward, so a rematerialised layer's backward never
runs the forward kernel again; without such a policy the tags do nothing.

All matmuls run on the MXU in float32 accumulation
(``preferred_element_type``) regardless of the bf16 inputs; masking (padded
keys, causal) is computed from ``broadcasted_iota`` against dynamic global
offsets held in SMEM, so the same kernels serve the standalone op (offsets
0) and every step of ring attention (offsets = ring position, see
``parallel/ring_attention.py``).

**Cells by class.**  :func:`tile_visible` says, from a grid cell's indices
alone, whether its tile holds any unmasked pair:

* *outside* (none: above the causal diagonal, past ``seq_len``, outside the
  window): not computed and not fetched.  Where the sequence offsets are
  static (Python ints, as the standalone op passes) the causal index maps
  clamp to the block's last (forward, dQ) or first (dK/dV, fused) visible
  tile, so
  consecutive outside cells repeat a block index and Pallas issues no copy.
  Under ring attention the offsets are traced scalars that an index map
  cannot read: the maps stay plain there and the cell is only skipped.
* *visited* (the rest): the masked body, wholly visible tiles included (a
  body without the mask for those measures 0.1% of a 16k-token train step
  on a v5e: PERF.md section 6, PR 27).

:func:`tile_census` counts the two over a shape's grids.  A ``scale`` that is
a power of two (a 64-wide head's 0.125) is folded, exactly, into q before
the kernels, which then multiply no score tile by it.

``window`` (causal sliding window: query ``t`` sees keys ``s`` with
``0 <= t - s < window``) does more than mask: the innermost grid dimension
shrinks to the tiles a block's window can touch and the index maps start at
the block's first such tile, so tiles outside the window are neither
fetched nor visited.  Grouped heads: ``k`` may carry fewer heads than ``q``
and ``v`` fewer still and a wider head (``H % Hk == 0``, ``H % Hv == 0``;
query head ``h`` reads key head ``h // (H / Hk)`` and value head
``h // (H / Hv)``) through the index maps alone, with no repeated copy;
dK/dV come out per query head and are summed over each group outside.
``dot_dtype`` feeds the MXU operands in that dtype (bfloat16 for a bf16
model; accumulation stays float32); the default keeps float32 operands.
Callers that pass none of the three get the kernels and grids they had.

On non-TPU backends the same kernels run under the Pallas interpreter
(``interpret=True``), which is how the CPU test suite checks parity against
``parallel.ring_attention.full_attention`` for values *and* gradients.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "tile_visible", "tile_census", "fused_bwd",
           "train_tiles_visited", "fused_bwd_census", "FLASH_RESIDUALS",
           "saved_fwd_census"]

_logger = logging.getLogger(__name__)

# the names the op's forward gives its output and its row statistics: a
# remat policy that saves them keeps the backward from running the forward
# kernel again (models/helpers.py:maybe_remat)
FLASH_RESIDUALS = ("flash_out", "flash_lse")

_NEG_INF = float("-inf")
_LANES = 128          # scalar-per-row scratch is lane-replicated to 128
# the fused backward's scoped VMEM (ops/ssd.py and ops/causal_conv.py set the
# same; a v5e core has 128 MiB) and the most of it the float32 dQ row of one
# head may be: 16 MiB is 32,768 positions of a 128-lane head.  The row is an
# output block, so Pallas holds two (the next head's fills while the last
# one's is written back), beside the tile's operands and (block_q, block_k)
# float32 intermediates, as in the split kernels.
_VMEM_LIMIT = 64 * 1024 * 1024
_DQ_ROW_BYTES = _VMEM_LIMIT // 4

_warned_interpreted = set()


def resolve_interpret(interpret: Optional[bool], kernel: str) -> bool:
    """A Pallas kernel's ``interpret`` default: compiled on TPU, interpreted
    elsewhere (how the CPU suite checks parity).  An interpreted run is
    never a measurement, so it is logged — once per kernel — at WARNING;
    chip paths assert the backend before relying on the default."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret and kernel not in _warned_interpreted:
        _warned_interpreted.add(kernel)
        _logger.warning("%s runs under the Pallas INTERPRETER (backend %r)"
                        " — correctness only, not the compiled kernel: the "
                        "interpreter visits the same tiles (window skipping "
                        "included) but times nothing",
                        kernel, jax.default_backend())
    return interpret


def _vmem_spec(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _smem_scalar_spec():
    """(1, 1) int32 scalar operand (offsets); scalars live in SMEM on TPU."""
    return pl.BlockSpec((1, 1), lambda *_: (0, 0), memory_space=pltpu.SMEM)


def _scratch(shape):
    """float32 VMEM scratch buffer declaration."""
    return pltpu.VMEM(shape, jnp.float32)


def _as_scalar(x) -> jnp.ndarray:
    return jnp.asarray(x, jnp.int32).reshape(1, 1)


def _out_struct(shape, dtype, like):
    """ShapeDtypeStruct whose varying-mesh-axes set matches ``like``.

    Inside ``shard_map`` (ring attention) pallas outputs must declare which
    mesh axes they vary over; inherit that from an input operand so the same
    kernels work standalone and under any mesh.
    """
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _tiles_in_window(block_a: int, block_b: int, window: int, n: int) -> int:
    """How many ``block_b`` tiles a ``block_a`` block's window can touch
    (at most ``n``): the span is ``block_a + window - 1`` positions at any
    alignment."""
    return min(n, (block_a + window - 2) // block_b + 2)


def _fdiv(x, c: int, b: int):
    """floor((x + c) / b) for ``x >= 0`` (a grid index or a Python int) and
    a static ``c`` of either sign, on a non-negative numerator so the
    truncating integer division is the floor."""
    w = max(0, -(c // b))
    return (x + (c + w * b)) // b - w


def _first_k_tile(iq, bq: int, bk: int, window: int):
    """First key tile the window of q block ``iq`` reaches:
    max(0, floor((iq*bq - window + 1) / bk))."""
    return jnp.maximum(_fdiv(iq * bq, 1 - window, bk), 0)


def _last_k_tile(iq, bq: int, bk: int, seq_len: int, off: int = 0):
    """Last key tile q block ``iq`` sees under the causal mask (``off`` =
    q_off - kv_off, static): the one its last row falls in, kept to the
    tiles that hold a valid key."""
    return jnp.clip(_fdiv((iq + 1) * bq - 1, off, bk), 0,
                    (seq_len - 1) // bk)


def _first_q_tile(jk, bq: int, bk: int, off: int = 0):
    """First query tile that can see key block ``jk`` (causal)."""
    return jnp.maximum(_fdiv(jk * bk, -off, bq), 0)


def _last_q_tile(jk, bq: int, bk: int, window: int, seq_len: int, nq: int,
                 off: int = 0):
    """Last query tile whose window still holds key block ``jk``'s last
    valid key, of the ``nq`` there are."""
    last_key = jnp.minimum(jk * bk + bk - 1, seq_len - 1)
    return jnp.clip(_fdiv(last_key + window - 1, -off, bq), 0, nq - 1)


def _k_tile(iq, jk, bq: int, bk: int, window):
    """The key tile cell ``(iq, jk)`` of a (bh, q block, k tile) grid reads:
    under a window the grid's axis starts at the block's first tile."""
    return jk if window is None else _first_k_tile(iq, bq, bk, window) + jk


def _q_tile(jk, iq, bq: int, bk: int, window):
    """The query tile cell ``(jk, iq)`` of the (bh, k block, q tile) grid
    reads: under a window the axis starts at the block's first tile (a
    window comes with offsets of 0: the standalone op's)."""
    return iq if window is None else _first_q_tile(jk, bq, bk) + iq


def tile_visible(i, j, block_q: int, block_k: int, seq_len: int,
                 causal: bool = False, window: Optional[int] = None,
                 q_off=0, kv_off=0, q_tiles: Optional[int] = None):
    """Does the cell that pairs query tile ``i`` with key tile ``j`` hold
    at least one unmasked (query, key) pair?  (Else the cell is **outside**.)
    A pair (t, s) of global positions ``t = q_off + row``, ``s = kv_off +
    key`` is unmasked iff the key is valid (``key < seq_len``) and, if
    ``causal``, ``s <= t`` and, under a ``window``, ``t - s < window``; rows
    count to the end of their block, padding included, as the kernels
    compute them.  ``q_tiles``: query tiles past it do not exist (a windowed
    dK/dV grid can name them).

    Comparisons and ``&`` only, so ``i``, ``j`` and the offsets may be
    Python ints, numpy arrays (the census, the tests) or the kernels'
    traced scalars; the kernels' predicates, the clamped index maps' tests
    and :func:`tile_census` all read this one function."""
    c0 = j * block_k
    visible = c0 < seq_len
    if q_tiles is not None:
        visible = visible & (i < q_tiles)
    if causal:
        r0 = q_off + i * block_q               # the block's first, last row
        r1 = r0 + block_q - 1
        k0 = kv_off + c0                       # the tile's first, last key
        k1 = k0 + block_k - 1
        visible = visible & (k0 <= r1)
        if window is not None:
            # the first row must still hold the tile's last VALID key
            visible = visible & (r0 - k1 < window) \
                & (r0 - (kv_off + seq_len - 1) < window)
    return visible


def _lanes(x, n: int):
    """A lane-replicated ``(rows, 128)`` value at ``n`` lanes: whole copies
    side by side (``pltpu.repeat``), cut to ``n`` where it is no multiple of
    128.  The forward's row statistics stay in this form from the load of
    their scratch to its store, so no ``(rows, 1)`` value is sliced out and
    broadcast back across a tile; every lane holds the row's one value, so
    the bits are those of a broadcast."""
    reps = -(-n // _LANES)
    if reps > 1:
        x = pltpu.repeat(x, reps, axis=1)
    return x if x.shape[1] == n else x[:, :n]


def _dots(dot_dtype):
    """(operand dtype, scale q before the score dot?).  float32 operands
    keep the original expression (q * scale, then the dot)."""
    if dot_dtype is None:
        return jnp.float32, True
    return dot_dtype, False


def _scaled(x, scale: float):
    """``x * scale``; nothing at all for the 1.0 the op passes once it has
    folded a power-of-two scale into q (:func:`flash_attention`)."""
    return x if scale == 1.0 else x * scale


def _invalid(iq, jt, bq: int, bk: int, seq_len: int, causal: bool, window,
             q_off, kv_off):
    """The (bq, bk) mask of a tile: True where the pair is masked."""
    k_loc = jt * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    invalid = k_loc >= seq_len
    if causal:
        q_pos = q_off + iq * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        invalid = jnp.logical_or(invalid, kv_off + k_loc > q_pos)
        if window is not None:
            invalid = jnp.logical_or(
                invalid, q_pos - (kv_off + k_loc) >= window)
    return invalid


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_off_ref, kv_off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, seq_len, causal,
                window=None, dot_dtype=None):
    """One (bh, q-block, k-tile) grid cell of the online softmax.

    ``q_off``/``kv_off`` are *global* sequence offsets of this Q shard / KV
    buffer — 0 standalone; under ring attention they locate the shard in the
    global sequence so the causal mask is right at every ring step.
    ``seq_len`` counts the valid (un-padded) keys in the KV buffer.
    """
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)
    q_off = q_off_ref[0, 0]
    kv_off = kv_off_ref[0, 0]

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    cd, scale_q = _dots(dot_dtype)
    jt = _k_tile(iq, jk, bq, bk, window)       # the key tile this cell reads

    @pl.when(tile_visible(iq, jt, bq, bk, seq_len, causal, window, q_off,
                          kv_off))
    def _accumulate():
        if scale_q:
            q = _scaled(q_ref[0].astype(jnp.float32), scale)
        else:
            q = q_ref[0].astype(cd)
        k = k_ref[0].astype(cd)
        v = v_ref[0].astype(cd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if not scale_q:
            s = _scaled(s, scale)
        invalid = _invalid(iq, jt, bq, bk, seq_len, causal, window, q_off,
                           kv_off)
        s = jnp.where(invalid, _NEG_INF, s)

        m_prev = m_ref[...]                                    # (BQ, 128)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # rows that have seen no valid key yet: keep exp() argument finite
        m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        # a masked score is -inf and m_safe finite, so its p is exp(-inf),
        # exactly 0: no second select over the tile
        p = jnp.exp(s - _lanes(m_safe, bk))
        corr = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * _lanes(corr, acc_ref.shape[1]) + \
            jax.lax.dot_general(p.astype(cd), v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(jk == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)                     # (BQ, 128)
        o_ref[0] = (acc_ref[:] / _lanes(l, acc_ref.shape[1])).astype(
            o_ref.dtype)
        m = m_ref[...]
        # lse keeps the statistics' layout (Mosaic requires the last two
        # block dims be (8·k, 128); the reference jax.experimental.pallas
        # TPU flash kernel's residuals have it too)
        lse_ref[0] = jnp.where(m == _NEG_INF, 0.0, m) + jnp.log(l)


def _static_off(q_off, kv_off) -> Optional[int]:
    """q_off - kv_off where an index map may use it: both plain ints (the
    standalone op's 0s), not ring attention's traced positions."""
    if isinstance(q_off, int) and isinstance(kv_off, int):
        return q_off - kv_off
    return None


def _kv_map(bh_q: int, bh_kv: int, tile=None):
    """Index map of a key or value operand on a (bh, q block, k tile) grid:
    query head ``b`` reads head ``b // group``; ``tile(i, j)`` names the
    key tile (default: ``j``).  The plain map where nothing is grouped."""
    g = bh_q // bh_kv
    if g == 1 and tile is None:
        return lambda b, i, j: (b, j, 0)
    tile = tile or (lambda i, j: j)
    return lambda b, i, j: (b // g, tile(i, j), 0)


def _k_tile_map(block_q, block_k, window, nk, causal=False, seq_len=None,
                off=None):
    """(k tiles on the grid, tile(i, j)) of the (bh, q block, k tile) grids:
    all of them, or under a window only those the block's window touches.
    An outside cell repeats the block of its row's nearest visited cell, so
    nothing is copied for it: with a static ``off`` the causal cells past
    the block's last visible tile name that tile; otherwise a window's are
    only kept in range (the kernel skips what a clamp repeats)."""
    nkt = nk if window is None else _tiles_in_window(block_q, block_k,
                                                     window, nk)
    clamp = causal and off is not None
    if window is None and not clamp:
        return nkt, None

    def tile(i, j):
        last = _last_k_tile(i, block_q, block_k, seq_len, off) if clamp \
            else nk - 1
        return jnp.minimum(_k_tile(i, j, block_q, block_k, window), last)
    return nkt, tile


def _q_tile_map(block_q, block_k, window, nq, causal=False, seq_len=None,
                off=None):
    """(q tiles on the grid, tile(j, i)) of the (bh, k block, q tile) grid,
    as :func:`_k_tile_map`: the causal cells before the block's first
    visible tile name that tile, a window's cells past its last name the
    last."""
    clamp = causal and off is not None
    if window is None:
        if not clamp:
            return nq, None
        return nq, lambda j, i: jnp.maximum(i, jnp.minimum(
            _first_q_tile(j, block_q, block_k, off), nq - 1))

    def tile(j, i):
        last = _last_q_tile(j, block_q, block_k, window, seq_len, nq, off) \
            if clamp else nq - 1
        return jnp.minimum(_q_tile(j, i, block_q, block_k, window), last)
    return _tiles_in_window(block_k, block_q, window, nq), tile


def _kernel_kwargs(window, dot_dtype):
    """Static arguments only the new paths pass on."""
    kw = {}
    if window is not None:
        kw["window"] = window
    if dot_dtype is not None:
        kw["dot_dtype"] = dot_dtype
    return kw


def _fwd(q, k, v, scale, block_q, block_k, causal, seq_len, interpret,
         q_off=0, kv_off=0, window=None, dot_dtype=None):
    """Padded-layout forward: (BH, Lq, D), (BHk, Lk, D), (BHv, Lk, Dv) →
    (out (BH, Lq, Dv), lse)."""
    bh, lpq, d = q.shape
    lpk, dv = k.shape[1], v.shape[2]
    nkt, tile = _k_tile_map(block_q, block_k, window, lpk // block_k, causal,
                            seq_len, _static_off(q_off, kv_off))
    grid = (bh, lpq // block_q, nkt)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, seq_len=seq_len,
                          causal=causal, **_kernel_kwargs(window, dot_dtype)),
        grid=grid,
        in_specs=[
            _smem_scalar_spec(),
            _smem_scalar_spec(),
            _vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, block_k, d), _kv_map(bh, k.shape[0], tile)),
            _vmem_spec((1, block_k, dv), _kv_map(bh, v.shape[0], tile)),
        ],
        out_specs=[
            _vmem_spec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((bh, lpq, dv), q.dtype, q),
            _out_struct((bh, lpq, _LANES), jnp.float32, q),
        ],
        scratch_shapes=[
            _scratch((block_q, dv)),
            _scratch((block_q, _LANES)),
            _scratch((block_q, _LANES)),
        ],
        interpret=interpret,
    )(_as_scalar(q_off), _as_scalar(kv_off), q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _tile_grads(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, it, jt,
                q_off, kv_off, *, scale, seq_len, causal, window, dot_dtype):
    """What every backward kernel makes of the cell that pairs query tile
    ``it`` with key tile ``jt``: ``p = exp(s - lse)`` under the mask and
    the FlashAttention-2 ``ds = p * (dp - delta)``, made in float32 and
    returned, like the operands, in the MXU's dtype.  One function, so the
    split pair and the fused kernel cannot drift apart.  Returns
    (q, k, do, p, ds)."""
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    cd, scale_q = _dots(dot_dtype)
    q = q_ref[0].astype(cd)
    k = k_ref[0].astype(cd)
    v = v_ref[0].astype(cd)
    do = do_ref[0].astype(cd)
    lse = lse_ref[0, :, :1]                                     # (BQ, 1)
    delta = delta_ref[0, :, :1]
    s = jax.lax.dot_general(_scaled(q, scale) if scale_q else q, k,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if not scale_q:
        s = _scaled(s, scale)
    invalid = _invalid(it, jt, bq, bk, seq_len, causal, window, q_off,
                       kv_off)
    p = jnp.where(invalid, 0.0, jnp.exp(s - lse))               # (BQ, BK)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = _scaled(p * (dp - delta), scale)
    return q, k, do, p.astype(cd), ds.astype(cd)


def _rows_dot(a, b):
    """aᵀ·b: the (BQ, BK) tile ``a`` contracted over its rows, float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _bwd_dkv_kernel(q_off_ref, kv_off_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, seq_len, causal, window=None, dot_dtype=None,
                    q_tiles=None):
    """One (bh, k-block, q-tile) grid cell accumulating dK, dV."""
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)
    q_off = q_off_ref[0, 0]
    kv_off = kv_off_ref[0, 0]

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    it = _q_tile(jk, iq, bq, bk, window)     # the query tile this cell reads

    # window: past the last query tile the clamped map repeats it
    @pl.when(tile_visible(it, jk, bq, bk, seq_len, causal, window, q_off,
                          kv_off, q_tiles))
    def _accumulate():
        q, _, do, p, ds = _tile_grads(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, it, jk, q_off,
            kv_off, scale=scale, seq_len=seq_len, causal=causal,
            window=window, dot_dtype=dot_dtype)
        dv_acc[:] += _rows_dot(p, do)
        dk_acc[:] += _rows_dot(ds, q)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_off_ref, kv_off_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_acc, *, scale, seq_len,
                   causal, window=None, dot_dtype=None):
    """One (bh, q-block, k-tile) grid cell accumulating dQ."""
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)
    q_off = q_off_ref[0, 0]
    kv_off = kv_off_ref[0, 0]

    @pl.when(jk == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    jt = _k_tile(iq, jk, bq, bk, window)

    @pl.when(tile_visible(iq, jt, bq, bk, seq_len, causal, window, q_off,
                          kv_off))
    def _accumulate():
        _, k, _, _, ds = _tile_grads(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, iq, jt, q_off,
            kv_off, scale=scale, seq_len=seq_len, causal=causal,
            window=window, dot_dtype=dot_dtype)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_fused_kernel(q_off_ref, kv_off_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dk_acc,
                      dv_acc, *, scale, seq_len, causal, window=None,
                      dot_dtype=None, q_tiles=None):
    """One (bh, k-block, q-tile) grid cell of the whole backward: the dK/dV
    kernel's cell, which also adds ``ds·k`` into its query tile's rows of
    ``dq_ref``, the head's whole float32 dQ row: an output block whose
    index names only the head, so it stays in VMEM across both inner grid
    axes and is written back when the head changes.  Key blocks reach every
    query tile in ascending order, the order :func:`_bwd_dq_kernel` adds
    them in."""
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)
    q_off = q_off_ref[0, 0]
    kv_off = kv_off_ref[0, 0]

    @pl.when((jk == 0) & (iq == 0))
    def _init_head():
        dq_ref[:] = jnp.zeros_like(dq_ref)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    it = _q_tile(jk, iq, bq, bk, window)

    @pl.when(tile_visible(it, jk, bq, bk, seq_len, causal, window, q_off,
                          kv_off, q_tiles))
    def _accumulate():
        q, k, do, p, ds = _tile_grads(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, it, jk, q_off,
            kv_off, scale=scale, seq_len=seq_len, causal=causal,
            window=window, dot_dtype=dot_dtype)
        dv_acc[:] += _rows_dot(p, do)
        dk_acc[:] += _rows_dot(ds, q)
        rows = pl.ds(pl.multiple_of(it * bq, bq), bq)
        dq_ref[0, rows, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _on_dkv_grid(fused, q, k, v, do, lse, delta, scale, block_q, block_k,
                 causal, seq_len, interpret, q_off, kv_off, window,
                 dot_dtype):
    """The launch on the (bh, k block, q tile) grid: (dK, dV) per query
    head, float32, from the dK/dV kernel or, ``fused``, the head's whole
    dQ row before them from the fused one."""
    bh, lpq, d = q.shape
    lpk, dv = k.shape[1], v.shape[2]
    nq = lpq // block_q
    kw = _kernel_kwargs(window, dot_dtype)
    if window is not None:
        kw["q_tiles"] = nq
    nqt, tile = _q_tile_map(block_q, block_k, window, nq, causal, seq_len,
                            _static_off(q_off, kv_off))
    q_map = (lambda b, j, i: (b, i, 0)) if tile is None else \
        (lambda b, j, i: (b, tile(j, i), 0))
    gk, gv = bh // k.shape[0], bh // v.shape[0]
    k_map = (lambda b, j, i: (b, j, 0)) if gk == 1 else \
        (lambda b, j, i: (b // gk, j, 0))
    v_map = (lambda b, j, i: (b, j, 0)) if gv == 1 else \
        (lambda b, j, i: (b // gv, j, 0))
    out_specs = [
        _vmem_spec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        _vmem_spec((1, block_k, dv), lambda b, j, i: (b, j, 0)),
    ]
    out_shape = [
        _out_struct((bh, lpk, d), jnp.float32, k),
        _out_struct((bh, lpk, dv), jnp.float32, k),
    ]
    params = {}
    if fused:
        # the head's whole row: its index names only the head
        out_specs.insert(0, _vmem_spec((1, lpq, d), lambda b, j, i: (b, 0, 0)))
        out_shape.insert(0, _out_struct((bh, lpq, d), jnp.float32, q))
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT)
    kern = functools.partial(
        _bwd_fused_kernel if fused else _bwd_dkv_kernel, scale=scale,
        seq_len=seq_len, causal=causal, **kw)
    return pl.pallas_call(
        kern,
        grid=(bh, lpk // block_k, nqt),
        in_specs=[
            _smem_scalar_spec(),
            _smem_scalar_spec(),
            _vmem_spec((1, block_q, d), q_map),                       # q
            _vmem_spec((1, block_k, d), k_map),                       # k
            _vmem_spec((1, block_k, dv), v_map),                      # v
            _vmem_spec((1, block_q, dv), q_map),                      # do
            _vmem_spec((1, block_q, _LANES), q_map),
            _vmem_spec((1, block_q, _LANES), q_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            _scratch((block_k, d)),
            _scratch((block_k, dv)),
        ],
        interpret=interpret,
        **params,
    )(_as_scalar(q_off), _as_scalar(kv_off), q, k, v, do, lse, delta)


def _bwd_dkv(q, k, v, do, lse, delta, scale, block_q, block_k, causal,
             seq_len, interpret, q_off=0, kv_off=0, window=None,
             dot_dtype=None):
    """dK, dV for one KV buffer, streaming Q tiles.  Padded layout.  With
    grouped heads the result is per QUERY head, (BH, Lk, ·): the caller sums
    each group."""
    return _on_dkv_grid(False, q, k, v, do, lse, delta, scale, block_q,
                        block_k, causal, seq_len, interpret, q_off, kv_off,
                        window, dot_dtype)


def _bwd_fused(q, k, v, do, lse, delta, scale, block_q, block_k, causal,
               seq_len, interpret, q_off=0, kv_off=0, window=None,
               dot_dtype=None):
    """(dQ, dK, dV) from one launch on the dK/dV grid (:func:`fused_bwd`
    says where), all float32 as :func:`_bwd_dq` and :func:`_bwd_dkv` give
    them; dK, dV per query head."""
    return _on_dkv_grid(True, q, k, v, do, lse, delta, scale, block_q,
                        block_k, causal, seq_len, interpret, q_off, kv_off,
                        window, dot_dtype)


def _bwd_dq(q, k, v, do, lse, delta, scale, block_q, block_k, causal,
            seq_len, interpret, q_off=0, kv_off=0, window=None,
            dot_dtype=None):
    """dQ for this Q shard against one KV buffer, streaming K tiles."""
    bh, lpq, d = q.shape
    lpk, dv = k.shape[1], v.shape[2]
    nkt, tile = _k_tile_map(block_q, block_k, window, lpk // block_k, causal,
                            seq_len, _static_off(q_off, kv_off))
    kern = functools.partial(_bwd_dq_kernel, scale=scale, seq_len=seq_len,
                             causal=causal,
                             **_kernel_kwargs(window, dot_dtype))
    return pl.pallas_call(
        kern,
        grid=(bh, lpq // block_q, nkt),
        in_specs=[
            _smem_scalar_spec(),
            _smem_scalar_spec(),
            _vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),   # q
            _vmem_spec((1, block_k, d), _kv_map(bh, k.shape[0], tile)),  # k
            _vmem_spec((1, block_k, dv), _kv_map(bh, v.shape[0], tile)),  # v
            _vmem_spec((1, block_q, dv), lambda b, i, j: (b, i, 0)),  # do
            _vmem_spec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=_vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((bh, lpq, d), jnp.float32, q),
        scratch_shapes=[_scratch((block_q, d))],
        interpret=interpret,
    )(_as_scalar(q_off), _as_scalar(kv_off), q, k, v, do, lse, delta)


def _lane_rows(x):
    """One value a row, (BH, L), lane-replicated to the kernels' (BH, L,
    128) layout of row statistics."""
    return jnp.broadcast_to(x[..., None], (*x.shape, _LANES))


def _delta(do, out):
    """δ = rowsum(dO ⊙ O), lane-replicated to match the lse layout."""
    return _lane_rows(jnp.sum(do.astype(jnp.float32) * out.astype(
        jnp.float32), axis=-1))


def _group_sum(x, heads: int):
    """(BH, L, D) per query head -> (heads, L, D): each group's sum."""
    if x.shape[0] == heads:
        return x
    return x.reshape(heads, x.shape[0] // heads, *x.shape[1:]).sum(axis=1)


def fused_bwd(lpq: int, d: int, static_offsets: bool = True) -> bool:
    """Does the backward run as the one fused kernel?  Where the sequence
    offsets are Python ints (the standalone op's zeros; ring attention's are
    traced, and it calls the split pair by name) and the head's float32 dQ
    row, ``lpq`` padded positions of ``d`` padded lanes, is within
    ``_DQ_ROW_BYTES`` of VMEM.  Else dK/dV and dQ are two launches.  A
    function of the shape alone, so a model can count its layers by it."""
    return static_offsets and lpq * d * 4 <= _DQ_ROW_BYTES


def _bwd_kernels(q, k, v, do, lse, delta, scale, block_q, block_k, causal,
                 seq_len, interpret, q_off=0, kv_off=0, window=None,
                 dot_dtype=None):
    """(dQ, dK, dV) of the padded layout by whichever form :func:`fused_bwd`
    names; dK, dV per query head."""
    args = (q, k, v, do, lse, delta, scale, block_q, block_k, causal,
            seq_len, interpret, q_off, kv_off, window, dot_dtype)
    if fused_bwd(q.shape[1], q.shape[2],
                 _static_off(q_off, kv_off) is not None):
        return _bwd_fused(*args)
    dk, dv = _bwd_dkv(*args)
    return _bwd_dq(*args), dk, dv


def _bwd(scale, block_q, block_k, causal, interpret, seq_len, res, g,
         window=None, dot_dtype=None):
    q, k, v, out, lse = res              # lse: (BH, Lq), one float a row
    do = g[0] if isinstance(g, (tuple, list)) else g
    dq, dk, dv = _bwd_kernels(q, k, v, do, _lane_rows(lse), _delta(do, out),
                              scale, block_q, block_k, causal, seq_len,
                              interpret, window=window, dot_dtype=dot_dtype)
    dk, dv = _group_sum(dk, k.shape[0]), _group_sum(dv, v.shape[0])
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

def _blocks(l: int, block_q: int, block_k: int):
    """(block_q, block_k, padded Lq, padded Lk) the op runs a length at."""
    block_q = min(block_q, _round_up(l, 128))
    block_k = min(block_k, _round_up(l, 128))
    return block_q, block_k, _round_up(l, block_q), _round_up(l, block_k)


def tile_census(l: int, block_q: int = 128, block_k: int = 128,
                causal: bool = False, window: Optional[int] = None) -> dict:
    """How much of each grid is work at a shape: per head, for each kernel
    of :func:`flash_attention` at these arguments, its grid's ``cells`` and
    of them ``outside`` / ``visited`` (:func:`tile_visible` over the same
    grid-to-tile functions the kernels use).  Static per compiled shape, so
    a count and not a measurement: ``fwd`` and ``dq`` share a grid, ``dkv``
    has the transposed one, and the fused backward ``bwd`` runs on
    ``dkv``'s (a step launches ``bwd`` or the pair, :func:`fused_bwd`)."""
    bq, bk, lpq, lpk = _blocks(l, block_q, block_k)
    nq, nk = lpq // bq, lpk // bk

    def count(visible):
        visited = int(np.asarray(visible).sum())
        return {"cells": visible.size, "outside": visible.size - visited,
                "visited": visited}

    nkt, _ = _k_tile_map(bq, bk, window, nk)
    i, j = np.meshgrid(np.arange(nq), np.arange(nkt), indexing="ij")
    qk = count(tile_visible(i, np.asarray(_k_tile(i, j, bq, bk, window)),
                            bq, bk, l, causal, window))
    nqt, _ = _q_tile_map(bq, bk, window, nq)
    j, i = np.meshgrid(np.arange(nk), np.arange(nqt), indexing="ij")
    dkv = count(tile_visible(np.asarray(_q_tile(j, i, bq, bk, window)), j,
                             bq, bk, l, causal, window, q_tiles=nq))
    return {"fwd": qk, "dkv": dkv, "dq": dict(qk), "bwd": dict(dkv)}


def _op_fuses(l: int, head_dim: int, block_q: int) -> bool:
    """:func:`fused_bwd` for :func:`flash_attention` over ``l`` tokens of
    ``head_dim``-wide query heads at ``block_q``, as the op pads them."""
    return fused_bwd(_blocks(l, block_q, block_q)[2],
                     _round_up(head_dim, _LANES))


def train_tiles_visited(l: int, head_dim: int, block_q: int = 128,
                        block_k: int = 128, causal: bool = False,
                        window: Optional[int] = None) -> int:
    """Grid cells with a visible pair that one query head's forward and
    backward launch over a row of ``l`` tokens: the forward's and the
    fused backward's, or the split pair's where :func:`fused_bwd` refuses
    the row (one forward: under remat the saved residuals,
    :func:`saved_fwd_census`, keep it from running again)."""
    census = tile_census(l, block_q, block_k, causal, window)
    kernels = ("fwd", "bwd") if _op_fuses(l, head_dim, block_q) \
        else ("fwd", "dkv", "dq")
    return sum(census[kernel]["visited"] for kernel in kernels)


def fused_bwd_census(layers: int, l: int, head_dim: int,
                     block_q: int = 128):
    """``layers`` attention layers over rows of ``l`` tokens by the form
    their backward takes, (fused, split): what a model's
    ``attn_bwd_layers`` reports."""
    return (layers, 0) if _op_fuses(l, head_dim, block_q) else (0, layers)


def saved_fwd_census(layers: int, remat_policy: str) -> int:
    """Of ``layers`` attention layers rematerialised under
    ``remat_policy`` (``models/helpers.py:maybe_remat``), those whose
    backward reuses the forward kernel's saved :data:`FLASH_RESIDUALS`
    instead of running it again: all of them under ``full`` and ``dots``,
    none where nothing is rematerialised.  What a train step's
    ``attn_fwd_saved_layers`` reports."""
    return layers if remat_policy in ("full", "dots") else 0


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None,
                    dot_dtype=None) -> jnp.ndarray:
    """Fused O(L) -memory attention.  Shapes ``(B, L, H, D) → (B, L, H, Dv)``
    (same convention as :func:`parallel.ring_attention.full_attention`).

    The Q buffer pads to a ``block_q`` multiple and the KV buffer to a
    ``block_k`` multiple (head dims to the 128-lane width); pad keys are
    masked inside the kernel, so any static shape works.  Gradients flow
    through a custom VJP whose backward is also Pallas.  ``interpret``
    defaults to True off-TPU so tests run on the CPU interpreter.

    ``window`` (needs ``causal``): query ``t`` sees keys ``t - window < s <=
    t``; tiles outside are skipped, not masked.  ``k`` ``(B, L, Hk, D)`` and
    ``v`` ``(B, L, Hv, Dv)`` may carry fewer heads than ``q`` (grouped, see
    the module's text) and ``v`` another head size.  ``dot_dtype`` is the
    MXU operands' dtype (default float32).
    """
    assert q.ndim == 4, f"expected (B, L, H, D), got {q.shape}"
    # one sequence length only: prep() folds (B, H) together and pads with
    # q's L, so a cross-attention Lk != Lq would die deep inside prep with an
    # opaque reshape error — reject it here instead
    b, l, h, d = q.shape
    hk, hv, d_v = k.shape[2], v.shape[2], v.shape[3]
    assert q.shape[:2] == k.shape[:2] == v.shape[:2] and k.shape[3] == d \
        and h % hk == 0 and h % hv == 0, (
        f"flash_attention needs one batch and length, one q/k head size and "
        f"grouped head counts (q{q.shape} k{k.shape} v{v.shape})")
    assert window is None or (causal and window > 0), \
        "window needs causal=True and a positive size"
    interpret = resolve_interpret(interpret, "flash_attention")
    scale = scale if scale is not None else d ** -0.5
    if math.frexp(scale)[0] == 0.5:
        # a power of two commutes with every rounding on the way (the cast
        # to the operands' dtype, the float32 accumulation, dQ's cast), so
        # it goes into q once, here, and out of every score tile; dQ gets
        # its factor back through this product's own gradient
        q, scale = q * scale, 1.0
    block_q, block_k, lpq, lpk = _blocks(l, block_q, block_k)
    kw = _kernel_kwargs(window, dot_dtype)

    def prep(x, lp):  # (B, L, H, D) -> (B*H, lp, Dp)
        hx, dx = x.shape[2], x.shape[3]
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * hx, l, dx)
        return jnp.pad(x, ((0, 0), (0, lp - l),
                           (0, _round_up(dx, 128) - dx)))

    @functools.partial(jax.custom_vjp, nondiff_argnums=())
    def _op(qp, kp, vp):
        out, _ = _fwd_call(qp, kp, vp)
        return out

    def _op_fwd(qp, kp, vp):
        out, lse = _fwd_call(qp, kp, vp)
        # tagged for a remat policy (FLASH_RESIDUALS): the result and the
        # residual are the one tagged ``out``, or the policy would save the
        # residual and still run the kernel again for the result; ``lse``
        # at one float a row, every lane of the kernel's array being equal
        out = checkpoint_name(out, FLASH_RESIDUALS[0])
        lse = checkpoint_name(lse[..., 0], FLASH_RESIDUALS[1])
        return out, (qp, kp, vp, out, lse)

    def _fwd_call(qp, kp, vp):
        return _fwd(qp, kp, vp, scale, block_q, block_k, causal, l,
                    interpret, **kw)

    def _op_bwd(res, g):
        return _bwd(scale, block_q, block_k, causal, interpret, l, res, g,
                    **kw)

    _op.defvjp(_op_fwd, _op_bwd)

    out = _op(prep(q, lpq), prep(k, lpk), prep(v, lpk))
    out = out[:, :l, :d_v].reshape(b, h, l, d_v)
    return jnp.transpose(out, (0, 2, 1, 3))
