"""granite-4.0-h-micro: Mamba-2 layers with a NoPE grouped-attention layer
among every ten, muP multipliers.

Source: ``huggingface.co/ibm-granite/granite-4.0-h-micro`` (config.json,
``model_type`` ``granitemoehybrid`` with no experts: the dense member of the
family).  The stack is driven by ``layer_types`` (published: attention at
layers 5, 15, 25, 35 of 40).  Every layer is

    x = x + residual_multiplier * Mixer(RMSNorm(x))
    x = x + residual_multiplier * MLP(RMSNorm(x))          (SwiGLU, no bias)

with the embedding times ``embedding_multiplier``, the scores times
``attention_multiplier`` (1/64, not 1/sqrt(64)), the logits over
``logits_scaling``, tied embedding, no positional encoding anywhere.

The Mamba-2 mixer: ``[z | xBC | dt] = in_proj(h)``; a causal depthwise
convolution (4 taps, bias) and silu over ``xBC``; ``[x | B | C]`` with ``x``
as 64 heads of 64 channels and ``B``, ``C`` (128 wide) shared by the heads;
``dt = softplus(dt + dt_bias)`` and ``a = -exp(A_log)`` a head;
``ops/ssd.py`` for the recurrence; the gate first and then the RMSNorm over
all 4096 channels (one norm group); ``out_proj``.  What the published config
does not say (no clamp on dt, the gate before the norm, one norm group)
follows the released implementation's defaults and is listed under
``assumed`` in ``benchmark/configs/granite4_h_micro_10l.json``.

The vocabulary may be held in part (``vocab_rows``): embedding and head then
keep rows ``[0, vocab_rows)`` and ids must lie below it; the held columns of
the logits are the uncut model's.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..losses import next_token_loss
from ..ops.causal_conv import causal_conv1d, causal_conv_census
from ..ops.flash_attention import (flash_attention, fused_bwd_census,
                                   train_tiles_visited)
from ..ops.ssd import ssd_scan
from ..registry import register_model
from .helpers import maybe_remat
# the step's init (Mamba-1 and Mamba-2 draw it alike) and the plain grouped
# causal attention are the SambaY family's
from .phi4flash import _dt_bias_init, dense_diff_scores

__all__ = ["Granite4H", "published_layer_types"]

MAMBA, ATTENTION = "mamba", "attention"

# The attention kernels' block (q and k alike).  1024 is what the probe of
# PERF.md section 6 (PR 27) chose on a v5e at 16,384 tokens for heads and
# values of 64 and 128; 64-wide values were not probed on their own.
_FLASH_BLOCK = 1024


def published_layer_types(n_layers: int = 40) -> Tuple[str, ...]:
    """The first ``n_layers`` of the published schedule: nine Mamba-2 layers
    to one attention layer, the attention layer sixth of every ten."""
    return tuple(ATTENTION if i % 10 == 5 else MAMBA
                 for i in range(n_layers))


def _a_log_init(key, shape, dtype=jnp.float32):
    """A = -exp(A_log) uniform in [-16, -1] (Mamba-2)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def gated_rms_norm(y, z, scale, eps: float):
    """The Mamba-2 mixer's output norm: the gate first, then one RMSNorm
    over all the channels, in float32."""
    g = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
    return g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                             + eps) * scale


class _Layer(nn.Module):
    """One layer of the stack: the mixer of its ``kind``, then the MLP."""
    kind: str
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    d_conv: int
    chunk: int
    residual_multiplier: float
    attention_multiplier: float
    eps: float
    attn_impl: str = "flash"
    scan_impl: Optional[str] = None
    dtype: Any = None

    def _dense(self, features, name):
        return nn.Dense(features, use_bias=False, dtype=self.dtype, name=name)

    def _norm(self, name):
        return nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name=name)

    @nn.compact
    def __call__(self, x, training: bool = False):
        del training                       # no dropout anywhere in the stack
        y = self._norm("norm1")(x)
        y = self._mamba(y) if self.kind == MAMBA else self._attention(y)
        x = x + self.residual_multiplier * y
        with jax.named_scope("mlp"):
            y = self._dense(2 * self.d_ff, "gate_up")(self._norm("norm2")(x))
            g, u = jnp.split(y, 2, axis=-1)
            y = self._dense(self.d_model, "down")(nn.silu(g) * u)
        return x + self.residual_multiplier * y

    def _mamba(self, x):
        """The Mamba-2 mixer.  The convolution, its bias and the silu are
        one op with its own backward, ops/causal_conv.py:causal_conv1d: two
        TPU kernels where whole tiles hold the row (a TPU backend, channels
        and tokens multiples of 128), the same passes as array
        operations elsewhere."""
        b, l, _ = x.shape
        h, p, n = self.ssm_heads, self.ssm_head_dim, self.d_state
        inner, conv = h * p, h * p + 2 * n
        with jax.named_scope("ssd_proj"):
            zxd = self._dense(inner + conv + h, "in_proj")(x)
            z, xbc, dt = jnp.split(zxd, [inner, inner + conv], axis=-1)
            dt_bias = self.param("dt_bias", _dt_bias_init, (h,))
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        with jax.named_scope("ssd_conv"):
            w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                           (self.d_conv, conv))
            bias = self.param("conv_bias", nn.initializers.zeros, (conv,))
            xbc = causal_conv1d(xbc, w, bias)
            u, bm, cm = jnp.split(xbc, [inner, inner + n], axis=-1)
        a_log = self.param("A_log", _a_log_init, (h,))
        skip = self.param("D", nn.initializers.ones, (h,))
        with jax.named_scope("ssd_scan"):
            y = ssd_scan(u.reshape(b, l, h, p), dt, -jnp.exp(a_log), bm, cm,
                         skip, chunk=self.chunk, impl=self.scan_impl)
        with jax.named_scope("ssd_norm"):
            scale = self.param("norm_scale", nn.initializers.ones, (inner,))
            g = gated_rms_norm(y.reshape(b, l, inner), z, scale, self.eps)
        with jax.named_scope("ssd_proj"):
            return self._dense(self.d_model, "out_proj")(g.astype(x.dtype))

    def _attention(self, x):
        b, l, _ = x.shape
        h, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        with jax.named_scope("attn_full"):
            qkv = self._dense((h + 2 * hk) * dh, "qkv")(x)
            q, k, v = jnp.split(qkv, [h * dh, (h + hk) * dh], axis=-1)
            q = q.reshape(b, l, h, dh)
            k, v = k.reshape(b, l, hk, dh), v.reshape(b, l, hk, dh)
            if self.attn_impl == "flash":
                o = flash_attention(
                    q, k, v, causal=True, scale=self.attention_multiplier,
                    block_q=_FLASH_BLOCK, block_k=_FLASH_BLOCK,
                    dot_dtype=jnp.bfloat16 if q.dtype == jnp.bfloat16
                    else None)
            else:
                o = dense_diff_scores(q, k, v, None,
                                      self.attention_multiplier)
            return self._dense(self.d_model, "out_proj")(
                o.reshape(b, l, h * dh))


class Granite4H(nn.Module):
    layer_types: Tuple[str, ...] = published_layer_types()
    vocab_rows: int = 100352
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    d_ff: int = 8192
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    d_state: int = 128
    d_conv: int = 4
    chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    eps: float = 1e-5
    attn_impl: str = "flash"
    # ops/ssd.py's form: None = its kernels on a TPU, array form elsewhere
    scan_impl: Optional[str] = None
    loss_chunk: int = 1024
    remat_policy: str = "none"
    dtype: Any = None
    default_cfg: Any = None
    # the sequence task: ids in, next-token loss out (train/steps.py)
    sequence_task = True

    def setup(self):
        assert set(self.layer_types) <= {MAMBA, ATTENTION}, self.layer_types
        self.embed = nn.Embed(self.vocab_rows, self.d_model,
                              embedding_init=nn.initializers.normal(0.02),
                              dtype=self.dtype)
        layer_cls = maybe_remat(_Layer, self.remat_policy)
        self.layers = [layer_cls(
            kind=kind, d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            d_ff=self.d_ff, ssm_heads=self.ssm_heads,
            ssm_head_dim=self.ssm_head_dim, d_state=self.d_state,
            d_conv=self.d_conv, chunk=self.chunk,
            residual_multiplier=self.residual_multiplier,
            attention_multiplier=self.attention_multiplier, eps=self.eps,
            attn_impl=self.attn_impl, scan_impl=self.scan_impl,
            dtype=self.dtype) for kind in self.layer_types]
        self.final_norm = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype)

    def hidden(self, ids, training: bool = False):
        """(batch, L) ids below ``vocab_rows`` -> final hidden states."""
        with jax.named_scope("embed"):
            x = self.embed(ids) * self.embedding_multiplier
        for layer in self.layers:
            x = layer(x, training)
        return self.final_norm(x)

    def attn_tiles_visited(self, seq_len: int) -> int:
        """Grid cells the attention kernels visit in one train step over one
        row of ``seq_len`` tokens: each attention layer's query heads times
        the forward's and the backward's counts
        (ops/flash_attention.py:train_tiles_visited).  Static per shape: a
        census.  0 where the dense path runs."""
        if self.attn_impl != "flash":
            return 0
        cells = train_tiles_visited(seq_len, self.head_dim, _FLASH_BLOCK,
                                    _FLASH_BLOCK, True)
        return self.layer_types.count(ATTENTION) * self.n_heads * cells

    def attn_bwd_layers(self, seq_len: int) -> Tuple[int, int]:
        """The attention layers by the form their backward takes over rows
        of ``seq_len`` tokens, (fused, split):
        ops/flash_attention.py:fused_bwd.  Static per shape: a census."""
        if self.attn_impl != "flash":
            return 0, 0
        return fused_bwd_census(self.layer_types.count(ATTENTION), seq_len,
                                self.head_dim, _FLASH_BLOCK)

    def ssd_chunks(self, seq_len: int) -> int:
        """Chunks the scan walks in sequence over one row of ``seq_len``
        tokens: one walk of every Mamba-2 layer (the backward's walk and a
        forward made again under remat are not counted again).  Static per
        shape: a census."""
        return self.layer_types.count(MAMBA) * \
            -(-seq_len // min(self.chunk, seq_len))

    def causal_conv_layers(self, seq_len: int) -> Tuple[int, int]:
        """The Mamba-2 layers by the form their causal convolution takes
        over rows of ``seq_len`` tokens, (kernels, array form):
        ops/causal_conv.py:causal_conv_impl.  Static per shape and backend:
        a census."""
        return causal_conv_census(
            self.layer_types.count(MAMBA), seq_len,
            self.ssm_heads * self.ssm_head_dim + 2 * self.d_state)

    def __call__(self, ids, training: bool = False):
        """Logits over the rows held, (batch, L, vocab_rows), float32."""
        x = self.hidden(ids, training)
        return jnp.dot(x, self.embed.embedding.T.astype(x.dtype),
                       preferred_element_type=jnp.float32) \
            / self.logits_scaling

    def sequence_loss(self, ids, targets, training: bool = False,
                      weight=None):
        """(mean next-token cross-entropy, token accuracy in percent) over
        the positions whose target is not negative, with the logits made a
        chunk of positions at a time."""
        x = self.hidden(ids, training)
        with jax.named_scope("lm_head_loss"):
            return next_token_loss(x, self.embed.embedding, targets,
                                   chunk=self.loss_chunk, weight=weight,
                                   logit_scale=1.0 / self.logits_scaling)


# the smoke-test size: every mechanism at widths a CPU test can afford
_TINY = dict(d_model=64, n_heads=4, n_kv_heads=1, head_dim=16, d_ff=128,
             ssm_heads=4, ssm_head_dim=32, d_state=16, chunk=8,
             loss_chunk=16)


def _entry(n_layers: int, vocab_rows: int, widths=None):
    def fn(pretrained=False, **kwargs):
        del pretrained
        for k in ("num_classes", "in_chans", "drop_rate", "drop_path_rate",
                  "global_pool"):
            kwargs.pop(k, None)            # the classifiers' arguments
        for k, v in dict(widths or {}, vocab_rows=vocab_rows,
                         layer_types=published_layer_types(n_layers)).items():
            kwargs.setdefault(k, v)
        return Granite4H(**kwargs)
    return fn


def _register():
    for name, args, doc in (
            ("granite4_h_micro", (40, 100352),
             "the published 40 layers, whole vocabulary (3.19B parameters)"),
            ("granite4_h_micro_10l", (10, 12544),
             "the first period of layer_types and 1/8 of the vocabulary rows "
             "(772M parameters): what one chip of an 8-way vocabulary-"
             "sharded, pipelined deployment holds"),
            ("granite4_h_micro_tiny", (10, 512, _TINY),
             "the ten-layer schedule at smoke-test widths (d 64, 512 rows)")):
        fn = _entry(*args)
        fn.__name__ = fn.__qualname__ = name
        fn.__module__ = __name__
        fn.__doc__ = f"granite-4.0-h-micro, {doc}."
        register_model(fn)


_register()
