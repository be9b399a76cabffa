"""Phi-4-mini-flash-reasoning: the SambaY decoder-hybrid-decoder stack.

Source: ``huggingface.co/microsoft/Phi-4-mini-flash-reasoning`` (config.json)
and arXiv 2507.06607; differential attention is arXiv 2410.05258.  The stack
is ``self_periods`` x [Mamba, sliding-window differential attention], one
producer pair [Mamba, full differential attention], then ``cross_periods`` x
[gated memory unit, cross differential attention]: the producer Mamba's
scan output (before its gate) is the memory every GMU gates, and the full
layer's keys and values are what every cross layer attends to.  Both travel
as ordinary values of the forward pass.  Every layer is
``x + Mixer(LN(x))`` then ``x + MLP(LN(x))`` (SwiGLU), LayerNorm with bias,
no positional encoding, tied embedding.

Sizes the published config leaves out follow Mamba-1's convention
(``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank`` ceil(d/16)).  The
query heads are laid out group-major: head ``(g*2 + i)*r + j`` is member
``i`` (the first or second softmax map) of the ``j``-th query pair that
reads KV pair ``g`` (``r`` query pairs to one KV pair), so that key head
``2g + i`` and value pair ``g`` follow from integer division alone.  The
release interleaves (head ``2p + i``); the two differ by a fixed permutation
of W_q's columns and W_o's rows.

The vocabulary may be held in part (``vocab_rows``): embedding and head then
keep rows ``[0, vocab_rows)`` and ids must lie below it; the held columns of
the logits are the uncut model's.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..losses import next_token_loss
from ..ops.causal_conv import causal_conv1d, causal_conv_census
from ..ops.flash_attention import (flash_attention, fused_bwd_census,
                                   train_tiles_visited)
from ..ops.selective_scan import selective_scan
from ..registry import register_model
from .helpers import maybe_remat

__all__ = ["Phi4Flash", "layer_schedule"]

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


def layer_schedule(self_periods: int, cross_periods: int) -> Tuple[str, ...]:
    return (MAMBA, WINDOW) * self_periods + (MAMBA, FULL) + \
        (GMU, CROSS) * cross_periods


def _flash_block(window: Optional[int]) -> int:
    """The attention kernels' block (q and k alike): what a layer's call
    passes and what the model's census counts.  The two constants come from
    one probe, on a v5e at 16,384 tokens and these head sizes (PERF.md
    section 6, PR 27): of ten block shapes 1024 x 1024 was the fastest
    that fits VMEM there (a layer's kernels 221.8 ms at 512, 120.6 at
    1024), and under the window of 512, 512 beat 256 and 1024 (30.7 against
    50.2 and 35.7 ms).  Another chip, length or head size wants its own
    probe."""
    return 512 if window is not None else 1024


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _a_log_init(key, shape, dtype=jnp.float32):
    del key
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)),
                            shape)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus(bias) log-uniform in [1e-3, 1e-1] (Mamba-1)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))


def dense_diff_scores(q, k, v, window: Optional[int], scale: float):
    """Masked softmax attention with grouped heads, the plain way: what
    ``attn_impl='full'`` computes in place of the kernel."""
    h, l = q.shape[2], q.shape[1]
    k = jnp.repeat(k, h // k.shape[2], axis=2)
    v = jnp.repeat(v, h // v.shape[2], axis=2)
    s = jnp.einsum("blhd,bmhd->bhlm", q, k).astype(jnp.float32) * scale
    t, m = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
    mask = m <= t
    if window is not None:
        mask = mask & (t - m < window)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhlm,bmhd->blhd", p.astype(v.dtype), v)


class _Layer(nn.Module):
    """One layer of the stack.  ``mem`` is what an earlier layer handed on:
    the producer's scan output for a GMU, (k, v) for a cross layer, () for
    the rest.  Returns (x, what this layer hands on)."""
    kind: str
    index: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    window: int
    attn_impl: str = "flash"
    scan_chunk: int = 128
    scan_impl: Optional[str] = None
    dtype: Any = None

    def _dense(self, features, name, use_bias=False):
        return nn.Dense(features, use_bias=use_bias, dtype=self.dtype,
                        name=name)

    @nn.compact
    def __call__(self, x, training: bool = False, mem=()):
        del training                       # no dropout anywhere in the stack
        ln = lambda name: nn.LayerNorm(epsilon=1e-5, dtype=self.dtype,  # noqa
                                       name=name)
        y = ln("ln1")(x)
        out = ()
        if self.kind == MAMBA:
            y, out = self._mamba(y)
        elif self.kind == GMU:
            with jax.named_scope("gmu"):
                g = nn.silu(self._dense(self.d_inner, "in_proj")(y))
                y = self._dense(self.d_model, "out_proj")(
                    g * mem.astype(g.dtype))
        else:
            y, out = self._attention(y, mem)
        x = x + y
        with jax.named_scope("mlp"):
            y = self._dense(2 * self.d_ff, "gate_up")(ln("ln2")(x))
            g, u = jnp.split(y, 2, axis=-1)
            x = x + self._dense(self.d_model, "down")(nn.silu(g) * u)
        return x, out

    def _mamba(self, x):
        """The Mamba mixer; also returns the scan's output, the producer's
        memory.  The convolution, its bias and the silu are one op with its
        own backward, ops/causal_conv.py:causal_conv1d: two TPU kernels
        where whole tiles hold the row (a TPU backend, channels and
        tokens multiples of 128), the same passes as array operations
        elsewhere."""
        d, n, r = self.d_inner, self.d_state, self.dt_rank
        with jax.named_scope("mamba_proj"):
            u, z = jnp.split(self._dense(2 * d, "in_proj")(x), 2, axis=-1)
        with jax.named_scope("mamba_conv"):
            w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                           (self.d_conv, d))
            b = self.param("conv_bias", nn.initializers.zeros, (d,))
            u = causal_conv1d(u, w, b)
        with jax.named_scope("mamba_proj"):
            dbc = self._dense(r + 2 * n, "x_proj")(u)
            delta, bm, cm = jnp.split(dbc, [r, r + n], axis=-1)
            dt_w = self.param("dt_proj_kernel", nn.initializers.lecun_normal(),
                              (r, d))
            dt_b = self.param("dt_proj_bias", _dt_bias_init, (d,))
            delta = jax.nn.softplus(
                jnp.dot(delta, dt_w.astype(delta.dtype)).astype(jnp.float32)
                + dt_b)
        a_log = self.param("A_log", _a_log_init, (d, n))
        skip = self.param("D", nn.initializers.ones, (d,))
        with jax.named_scope("mamba_scan"):
            y = selective_scan(u, delta, -jnp.exp(a_log), bm, cm, skip,
                               chunk=self.scan_chunk, impl=self.scan_impl)
        with jax.named_scope("mamba_proj"):
            out = self._dense(self.d_model, "out_proj")(y * nn.silu(z))
        return out, y

    def _attention(self, x, mem):
        b, l, _ = x.shape
        h, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        pk = hk // 2                       # KV pairs
        r = h // hk                        # query pairs to one KV pair
        scope = {WINDOW: "attn_window", FULL: "attn_full",
                 CROSS: "attn_cross"}[self.kind]
        with jax.named_scope(scope):
            if self.kind == CROSS:
                q = self._dense(h * dh, "q", use_bias=True)(x)
                k, v = mem
            else:
                qkv = self._dense((h + 2 * hk) * dh, "qkv", use_bias=True)(x)
                q, k, v = jnp.split(qkv, [h * dh, (h + hk) * dh], axis=-1)
                k = k.reshape(b, l, hk, dh)
                v = v.reshape(b, l, pk, 2 * dh)
            q = q.reshape(b, l, h, dh)
            window = self.window if self.kind == WINDOW else None
            scale = dh ** -0.5
            if self.attn_impl == "flash":
                blk = _flash_block(window)
                o = flash_attention(
                    q, k, v, causal=True, window=window, scale=scale,
                    block_q=blk, block_k=blk,
                    dot_dtype=jnp.bfloat16 if q.dtype == jnp.bfloat16
                    else None)
            else:
                o = dense_diff_scores(q, k, v, window, scale)
            lam = [self.param(f"lambda_{n}", nn.initializers.normal(0.1),
                              (dh,)) for n in ("q1", "k1", "q2", "k2")]
            li = lambda_init(self.index)
            lam = jnp.exp(jnp.sum(lam[0] * lam[1])) \
                - jnp.exp(jnp.sum(lam[2] * lam[3])) + li
            o = o.reshape(b, l, pk, 2, r, 2 * dh).astype(jnp.float32)
            o = o[:, :, :, 0] - lam * o[:, :, :, 1]       # (b, l, pk, r, 2dh)
            sub = self.param("subln_scale", nn.initializers.ones, (2 * dh,))
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + 1e-5) * sub * (1.0 - li)
            o = o.reshape(b, l, h * dh).astype(x.dtype)
            y = self._dense(self.d_model, "out_proj", use_bias=True)(o)
        return y, ((k, v) if self.kind == FULL else ())


class Phi4Flash(nn.Module):
    self_periods: int = 8
    cross_periods: int = 7
    vocab_rows: int = 200064
    d_model: int = 2560
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    d_ff: int = 10240
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None
    window: int = 512
    attn_impl: str = "flash"
    scan_chunk: int = 128
    # ops/selective_scan.py's form: None = its kernels on a TPU, lax elsewhere
    scan_impl: Optional[str] = None
    loss_chunk: int = 1024
    remat_policy: str = "none"
    dtype: Any = None
    default_cfg: Any = None
    # the sequence task: ids in, next-token loss out (train/steps.py)
    sequence_task = True

    def setup(self):
        self.schedule = layer_schedule(self.self_periods, self.cross_periods)
        self.embed = nn.Embed(self.vocab_rows, self.d_model,
                              embedding_init=nn.initializers.normal(0.02),
                              dtype=self.dtype)
        layer_cls = maybe_remat(_Layer, self.remat_policy)
        self.layers = [layer_cls(
            kind=kind, index=i, d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            d_ff=self.d_ff, d_inner=self.expand * self.d_model,
            d_state=self.d_state, d_conv=self.d_conv,
            dt_rank=self.dt_rank or -(-self.d_model // 16),
            window=self.window, attn_impl=self.attn_impl,
            scan_chunk=self.scan_chunk, scan_impl=self.scan_impl,
            dtype=self.dtype)
            for i, kind in enumerate(self.schedule)]
        self.final_ln = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype)

    def hidden(self, ids, training: bool = False):
        """(batch, L) ids below ``vocab_rows`` -> final hidden states."""
        with jax.named_scope("embed"):
            x = self.embed(ids)
        memory = kv = ()
        for kind, layer in zip(self.schedule, self.layers):
            mem = memory if kind == GMU else kv if kind == CROSS else ()
            x, out = layer(x, training, mem)
            if kind == MAMBA:
                memory = out            # the last Mamba layer is the producer
            elif kind == FULL:
                kv = out
        return self.final_ln(x)

    def _attn_layer_windows(self):
        """Each attention layer's window (None: the whole causal row)."""
        return [self.window if kind == WINDOW else None
                for kind in layer_schedule(self.self_periods,
                                           self.cross_periods)
                if kind in (WINDOW, FULL, CROSS)]

    def attn_tiles_visited(self, seq_len: int) -> int:
        """Grid cells the attention kernels visit in one train step over one
        row of ``seq_len`` tokens: each attention layer's query heads times
        the forward's and the backward's counts
        (ops/flash_attention.py:train_tiles_visited; under remat the
        forward's saved output keeps it from running again).  Static per
        shape: a census, not
        a measurement.  0 where the dense path runs."""
        if self.attn_impl != "flash":
            return 0
        return self.n_heads * sum(
            train_tiles_visited(seq_len, self.head_dim, _flash_block(window),
                                _flash_block(window), True, window)
            for window in self._attn_layer_windows())

    def attn_bwd_layers(self, seq_len: int) -> Tuple[int, int]:
        """The attention layers by the form their backward takes over rows
        of ``seq_len`` tokens, (fused, split):
        ops/flash_attention.py:fused_bwd.  Static per shape: a census."""
        if self.attn_impl != "flash":
            return 0, 0
        windows = self._attn_layer_windows()
        fused = split = 0
        for window in set(windows):         # the window's blocks, the full's
            f, s = fused_bwd_census(windows.count(window), seq_len,
                                    self.head_dim, _flash_block(window))
            fused, split = fused + f, split + s
        return fused, split

    def causal_conv_layers(self, seq_len: int) -> Tuple[int, int]:
        """The Mamba layers by the form their causal convolution takes over
        rows of ``seq_len`` tokens, (kernels, array form):
        ops/causal_conv.py:causal_conv_impl.  Static per shape and backend:
        a census."""
        return causal_conv_census(
            layer_schedule(self.self_periods,
                           self.cross_periods).count(MAMBA),
            seq_len, self.expand * self.d_model)

    def __call__(self, ids, training: bool = False):
        """Logits over the rows held, (batch, L, vocab_rows), float32."""
        x = self.hidden(ids, training)
        return jnp.dot(x, self.embed.embedding.T.astype(x.dtype),
                       preferred_element_type=jnp.float32)

    def sequence_loss(self, ids, targets, training: bool = False,
                      weight=None):
        """(mean next-token cross-entropy, token accuracy in percent) over
        the positions whose target is not negative, with the logits made a
        chunk of positions at a time."""
        x = self.hidden(ids, training)
        with jax.named_scope("lm_head_loss"):
            return next_token_loss(x, self.embed.embedding, targets,
                                   chunk=self.loss_chunk, weight=weight)


# the smoke-test size: every mechanism at widths a CPU test can afford
_TINY = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
             window=16, scan_chunk=8, loss_chunk=16)


def _entry(self_periods: int, cross_periods: int, vocab_rows: int,
           widths=None):
    def fn(pretrained=False, **kwargs):
        del pretrained
        for k in ("num_classes", "in_chans", "drop_rate", "drop_path_rate",
                  "global_pool"):
            kwargs.pop(k, None)            # the classifiers' arguments
        for k, v in dict(widths or {}, self_periods=self_periods,
                         cross_periods=cross_periods,
                         vocab_rows=vocab_rows).items():
            kwargs.setdefault(k, v)
        return Phi4Flash(**kwargs)
    return fn


def _register():
    for name, args, doc in (
            ("phi4_mini_flash", (8, 7, 200064),
             "the published 32 layers, whole vocabulary (3.85B parameters)"),
            ("phi4_mini_flash_6l", (1, 1, 25008),
             "one period of each part and 1/8 of the vocabulary rows "
             "(697M parameters): what one chip of an 8-way vocabulary-"
             "sharded, pipelined deployment holds"),
            ("phi4_mini_flash_tiny", (1, 1, 512, _TINY),
             "the six-layer schedule at smoke-test widths (d 64, 512 rows)")):
        fn = _entry(*args)
        fn.__name__ = fn.__qualname__ = name
        fn.__module__ = __name__
        fn.__doc__ = f"Phi-4-mini-flash-reasoning, {doc}."
        register_model(fn)


_register()
