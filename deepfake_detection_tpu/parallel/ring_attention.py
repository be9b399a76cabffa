"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has no sequence models — its "temporal" axis is 4 frames
channel-concatenated (SURVEY.md §5) — but long-context attention is a
first-class requirement for the TPU framework (it backs the ViT/TimeSformer
families).  Two standard schemes, both expressed over
a mesh axis with XLA collectives riding ICI:

* **Ring attention** (Liu et al. 2023, blockwise; PAPERS.md): each device
  holds one sequence block of Q/K/V.  K/V blocks rotate around the ring via
  ``lax.ppermute`` while each device accumulates its queries' attention with
  a numerically-stable online softmax (flash-attention style running max /
  denominator).  Communication overlaps with the block matmuls; memory is
  O(L/n) per device.
* **Ulysses** (DeepSpeed-Ulysses): ``all_to_all`` re-shards from
  sequence-split to head-split, runs *local* full attention on the head
  shard, and re-shards back.  Cheaper collectives for moderate L, requires
  heads % n == 0.

Both are plain functions over *local* blocks with an ``axis_name`` — usable
directly inside ``shard_map``; :func:`ring_self_attention` wraps the
shard_map boilerplate over a mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = ["ring_attention", "ring_flash_attention", "ulysses_attention",
           "ring_self_attention", "full_attention"]


def full_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   causal: bool = False, scale: Optional[float] = None
                   ) -> jnp.ndarray:
    """Reference dense attention (single device) for parity tests.

    Shapes: (B, L, H, D) → (B, L, H, D).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        mask = jnp.arange(lk)[None, :] > jnp.arange(lq)[:, None]
        s = jnp.where(mask[None, None], -jnp.inf, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = False,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Blockwise ring attention over local (B, L_local, H, D) blocks.

    Call inside ``shard_map`` with the sequence dim sharded over
    ``axis_name``.  K/V rotate ``axis_size`` times; accumulation is float32.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    b, lq, h, d = q.shape
    lk = k.shape[1]

    q32 = q.astype(jnp.float32) * scale
    q_pos = idx * lq + jnp.arange(lq)                      # global query rows
    perm = [(i, (i + 1) % n) for i in range(n)]

    def accumulate(t, k_blk, v_blk, acc, m, l):
        """Fold block (idx - t) mod n into the online-softmax accumulators."""
        src = (idx - t) % n
        s = jnp.einsum("bqhd,bkhd->bhqk", q32,
                       k_blk.astype(jnp.float32))          # (B,H,Lq,Lk)
        if causal:
            k_pos = src * lk + jnp.arange(lk)
            mask = k_pos[None, :] > q_pos[:, None]          # (Lq, Lk)
            s = jnp.where(mask[None, None], -jnp.inf, s)
        m_blk = jnp.max(s, axis=-1)                         # (B,H,Lq)
        m_new = jnp.maximum(m, m_blk)
        # guard fully-masked rows (m_new == -inf) against NaNs
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return acc_new, m_new, l_new

    def body(t, carry):
        k_blk, v_blk, acc, m, l = carry
        acc, m, l = accumulate(t, k_blk, v_blk, acc, m, l)
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return k_nxt, v_nxt, acc, m, l

    # mark the fresh accumulators as device-varying over the ring axis so the
    # fori_loop carry type matches the (sharded, hence varying) K/V blocks
    # (pre-0.6 jax has no varying-manual-axes type system — no-op there)
    def vary(x):
        return lax.pcast(x, axis_name, to="varying")
    acc0 = vary(jnp.zeros((b, lq, h, d), jnp.float32))
    m0 = vary(jnp.full((b, h, lq), -jnp.inf, jnp.float32))
    l0 = vary(jnp.zeros((b, h, lq), jnp.float32))
    # n-1 rotated steps, then fold the final resident block without the dead
    # trailing ppermute pair
    k_f, v_f, acc, m, l = lax.fori_loop(0, n - 1, body,
                                        (k, v, acc0, m0, l0))
    acc, m, l = accumulate(n - 1, k_f, v_f, acc, m, l)
    l = jnp.maximum(l, 1e-20)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _merge_blocks(o, lse, o_b, lse_b):
    """Fold a new normalized block result into the running (o, lse).

    Given per-block outputs already normalized by their own softmax
    denominators ``l_i = exp(lse_i)``, the exact combination is
    ``o = (l₁·o₁ + l₂·o₂) / (l₁ + l₂)`` — computed in log-space for
    stability.  This is how independently-flash-attended KV blocks compose
    (same identity FlashAttention-2 uses across its K tiles).
    """
    m = jnp.maximum(lse, lse_b)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    w = jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(lse - m_safe))
    w_b = jnp.where(jnp.isneginf(lse_b), 0.0, jnp.exp(lse_b - m_safe))
    tot = jnp.maximum(w + w_b, 1e-30)
    o_new = (w[..., None] * o + w_b[..., None] * o_b) / tot[..., None]
    return o_new, m_safe + jnp.log(tot)


def ring_flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         axis_name: str, causal: bool = False,
                         scale: Optional[float] = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Ring attention with fused Pallas flash blocks (the TPU production
    path; :func:`ring_attention` is the pure-XLA reference).

    Same calling convention as :func:`ring_attention` — local
    ``(B, L_local, H, D)`` blocks inside ``shard_map``, K/V rotating via
    ``lax.ppermute`` — but each resident block is attended by the
    flash-attention kernel (ops/flash_attention.py), so the (Lq, Lk) score
    tile never leaves VMEM: O(L_local) HBM traffic per step instead of the
    XLA path's materialized per-block score matrices.  Per-block results
    merge via the log-space identity in :func:`_merge_blocks`.

    The backward is the ring schedule from the Ring Attention paper
    (PAPERS.md): dK/dV accumulators travel the ring *with* their K/V blocks
    (arriving home after the full cycle with every device's contribution)
    while dQ accumulates locally; each per-block gradient is the Pallas
    backward kernel pair, reusing the forward's global logsumexp.
    """
    from ..ops.flash_attention import (_bwd_dkv, _bwd_dq, _fwd, _round_up,
                                       resolve_interpret)

    n = lax.axis_size(axis_name)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale_ = scale if scale is not None else d ** -0.5
    interpret = resolve_interpret(interpret, "ring_flash_attention")
    block_q = min(block_q, _round_up(lq, 128))
    block_k = min(block_k, _round_up(lk, 128))
    lpq, lpk = _round_up(lq, block_q), _round_up(lk, block_k)
    dp = _round_up(d, 128)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def prep(x, l, lp):                     # (B, l, H, D) -> (BH, lp, Dp)
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, l, d)
        return jnp.pad(x, ((0, 0), (0, lp - l), (0, dp - d)))

    def unprep(x, l):                       # (BH, lp, Dp) -> (B, l, H, D)
        x = x[:, :l, :d].reshape(b, h, l, d)
        return jnp.transpose(x, (0, 2, 1, 3))

    def vary(x):
        return lax.pcast(x, axis_name, to="varying")

    # K/V (and dK/dV in the backward) travel the ring in their raw
    # (B, l, H, D) layout: the ppermute link is the scarce ICI resource,
    # and padding to (BH, lp, 128·k) is a cheap *local* copy done fresh at
    # each step inside the kernel call.
    #
    # The device's ring position enters as a (float) operand, not a closure:
    # custom_vjp functions must not close over traced values.
    def _block_fwd(t, idx, qp, k_blk, v_blk):
        src = (idx - t) % n
        o_b, lse_b = _fwd(qp, prep(k_blk, lk, lpk), prep(v_blk, lk, lpk),
                          scale_, block_q, block_k, causal, lk, interpret,
                          q_off=idx * lq, kv_off=src * lk)
        return o_b, lse_b[:, :, 0]       # lse arrives lane-replicated

    @jax.custom_vjp
    def _op(idx_f, q, k, v):
        out, _ = _op_fwd(idx_f, q, k, v)
        return out

    def _op_fwd(idx_f, q, k, v):
        idx = idx_f.astype(jnp.int32)
        qp = prep(q, lq, lpq)

        def body(t, carry):
            k_blk, v_blk, o, lse = carry
            o_b, lse_b = _block_fwd(t, idx, qp, k_blk, v_blk)
            o, lse = _merge_blocks(o, lse, o_b.astype(jnp.float32), lse_b)
            return (lax.ppermute(k_blk, axis_name, perm),
                    lax.ppermute(v_blk, axis_name, perm), o, lse)

        o0 = vary(jnp.zeros((b * h, lpq, dp), jnp.float32))
        lse0 = vary(jnp.full((b * h, lpq), -jnp.inf, jnp.float32))
        # n-1 rotated steps + final resident block (no dead trailing permute)
        k_f, v_f, o, lse = lax.fori_loop(0, n - 1, body, (k, v, o0, lse0))
        o_b, lse_b = _block_fwd(n - 1, idx, qp, k_f, v_f)
        o, lse = _merge_blocks(o, lse, o_b.astype(jnp.float32), lse_b)
        out_p = o.astype(q.dtype)
        return unprep(out_p, lq), (idx_f, q, k, v, out_p, lse)

    def _op_bwd(res, g):
        from ..ops.flash_attention import _LANES, _delta
        idx_f, q, k, v, out_p, lse2 = res
        idx = idx_f.astype(jnp.int32)
        qp = prep(q, lq, lpq)
        do = prep(g, lq, lpq).astype(jnp.float32)
        delta = _delta(do, out_p)
        # kernels expect the lane-replicated lse layout
        lse = jnp.broadcast_to(lse2[..., None], (*lse2.shape, _LANES))

        def body(t, carry):
            k_blk, v_blk, dk_blk, dv_blk, dq = carry
            src = (idx - t) % n
            kp_t = prep(k_blk, lk, lpk)
            vp_t = prep(v_blk, lk, lpk)
            dk_p, dv_p = _bwd_dkv(qp, kp_t, vp_t, do, lse, delta, scale_,
                                  block_q, block_k, causal, lk, interpret,
                                  q_off=idx * lq, kv_off=src * lk)
            dq_p = _bwd_dq(qp, kp_t, vp_t, do, lse, delta, scale_,
                           block_q, block_k, causal, lk, interpret,
                           q_off=idx * lq, kv_off=src * lk)
            # dK/dV ride the ring with their block (raw layout, f32): after
            # the full cycle each block is home with every device's
            # contribution
            return (lax.ppermute(k_blk, axis_name, perm),
                    lax.ppermute(v_blk, axis_name, perm),
                    lax.ppermute(dk_blk + unprep(dk_p, lk), axis_name, perm),
                    lax.ppermute(dv_blk + unprep(dv_p, lk), axis_name, perm),
                    dq + dq_p)

        dk0 = vary(jnp.zeros((b, lk, h, d), jnp.float32))
        dv0 = vary(jnp.zeros((b, lk, h, d), jnp.float32))
        dq0 = vary(jnp.zeros((b * h, lpq, dp), jnp.float32))
        _, _, dk, dv, dq = lax.fori_loop(
            0, n, body, (k, v, dk0, dv0, dq0))
        return (jnp.zeros_like(idx_f), unprep(dq, lq).astype(q.dtype),
                dk.astype(k.dtype), dv.astype(v.dtype))

    _op.defvjp(_op_fwd, _op_bwd)
    idx_f = lax.axis_index(axis_name).astype(jnp.float32)
    return _op(idx_f, q, k, v).astype(q.dtype)


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      axis_name: str, causal: bool = False,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """All-to-all sequence parallelism over local (B, L_local, H, D) blocks.

    Re-shards seq→heads, runs dense local attention on H/n heads over the
    full sequence, re-shards back.  Requires ``H % axis_size == 0``.
    """
    n = lax.axis_size(axis_name)
    assert q.shape[2] % n == 0, f"heads {q.shape[2]} not divisible by {n}"

    def to_heads(x):  # (B, L/n, H, D) -> (B, L, H/n, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_seq(x):    # (B, L, H/n, D) -> (B, L/n, H, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    out = full_attention(to_heads(q), to_heads(k), to_heads(v),
                         causal=causal, scale=scale)
    return to_seq(out)


def ring_self_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        mesh: Mesh, seq_axis: str = "data",
                        causal: bool = False,
                        impl: str = "ring") -> jnp.ndarray:
    """shard_map wrapper: global (B, L, H, D) arrays, sequence sharded over
    ``seq_axis`` of ``mesh``; batch replicated across that axis.

    ``impl='ring_flash'`` fuses each per-block attention into the Pallas
    flash kernel (the TPU production path).  Off-TPU its shard_map sets
    ``check_vma=False`` because the Pallas *interpreter* mixes its own
    non-varying block counters with varying refs, which the vma checker
    rejects — on TPU (compiled Mosaic) the check stays on.
    """
    fn = {"ring": ring_attention, "ring_flash": ring_flash_attention,
          "ulysses": ulysses_attention}[impl]
    spec = P(None, seq_axis, None, None)
    interpreted_flash = (impl == "ring_flash"
                         and jax.default_backend() != "tpu")
    sharded = shard_map(
        functools.partial(fn, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=not interpreted_flash)
    return sharded(q, k, v)
