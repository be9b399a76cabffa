"""LFM2-24B-A2B: gated short convolutions with a RoPE / QK-norm grouped
attention layer among every four, a routed expert layer after the leading
dense ones.

Source: ``huggingface.co/LiquidAI/LFM2-24B-A2B`` (config.json,
``model_type`` ``lfm2_moe``).  The stack is driven by ``layer_types``
(published: 30 ``conv`` to 10 ``full_attention`` of 40) and
``num_dense_layers`` (published 2).  ``d`` = 2048, RMSNorm with a learned
scale, no bias anywhere.  Every layer is

    h = x + Mixer(RMSNorm_op(x));   x' = h + FFN(RMSNorm_ffn(h))

**Mixer "conv"** (gated short convolution): ``[B | C | u] = in_proj(z)``
(d -> 3d); ``y = out_proj(C * conv(B * u))`` with a causal depthwise
convolution of ``conv_L_cache`` = 3 taps, no bias, no activation
(``ops/causal_conv.py``).  **Mixer "full_attention"**: 32 query heads to 8
key/value heads of 64; q and k are RMS-normalised over the head's 64
channels, then rotated (theta 1e6, rotate-half pairs, positions 0..L-1 a
row); causal softmax of ``q k^T / 8``.

**FFN** of a layer below ``num_dense_layers``: SwiGLU of width 11776.
Otherwise the mixture of experts (``ops/moe.py``): ``s = sigmoid(gate(z))``
over all ``E`` = 64 experts in float32; the top 4 of ``s + expert_bias`` are
selected; they weigh ``s_e / (sum of the selected s + 1e-6) *
routed_scaling_factor``; ``FFN(z) = sum_e w_e * w2_e(silu(w1_e z) * w3_e
z)``, width 1536, over the selected experts **that this chip holds**
(``held = (first, count)``).  The sum of that over the shares of a
deployment is the published layer: the router is 64 wide and the
normalisation is over all four selected whether held here or not.
``expert_bias`` is a buffer outside the optimizer (the ``batch_stats``
collection), constant in training.

What the published config does not say (tied embedding and a final RMSNorm,
the norm before the rotation, the pairing, the 1e-6, the layouts) is listed
under ``assumed`` in ``benchmark/configs/lfm2_24b_a2b_5l.json``.

The vocabulary may be held in part (``vocab_rows``), as in
``models/granite4h.py``.  A layer that routes sows its counts (tokens
routed, assignments on held experts, the fullest held expert's, that times
the experts held, whether the pass took every row: ``ops/moe.py``) into the
``moe_counts`` collection, which ``train/steps.py`` sums into the step's
metrics.
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
from ..ops.moe import expert_ffn, moe_census, route, routing_counts
from ..registry import register_model
from .helpers import maybe_remat
# the plain grouped causal attention is the SambaY family's
from .phi4flash import dense_diff_scores

__all__ = ["Lfm2Moe", "published_layer_types", "rope"]

CONV, ATTENTION = "conv", "full_attention"

# The attention kernels' block (q and k alike): granite's, whose head shapes
# these are (PERF.md section 6, PR 27).
_FLASH_BLOCK = 1024


def published_layer_types(n_layers: int = 40) -> Tuple[str, ...]:
    """The published schedule: attention at 2, 6, ..., 38, gated short
    convolutions elsewhere (and at 39)."""
    return tuple(ATTENTION if i % 4 == 2 and i < 39 else CONV
                 for i in range(n_layers))


def rope(x, theta: float):
    """Rotary positions over (batch, L, heads, dh): channel ``i`` of the
    first half pairs with channel ``i + dh / 2`` (rotate-half), the pair
    turned by ``position * theta ** (-2 i / dh)``.  Float32 inside."""
    l, dh = x.shape[1], x.shape[3]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.concatenate([f(ang)] * 2, -1)[None, :, None, :]
                for f in (jnp.cos, jnp.sin))
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return (xf * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)


def gated_short_conv(b, c, u, w):
    """``C * conv(B * u)``: the input gate before the three causal taps, the
    output gate after them; no bias, no activation."""
    return c * causal_conv1d(b * u, w, None, activation=None)


class _Layer(nn.Module):
    """One layer of the stack: the mixer of its ``kind``, then the dense
    MLP (``dense``) or the routed experts held here."""
    kind: str
    dense: bool
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    d_expert: int
    n_experts: int
    top_k: int
    held: Tuple[int, int]
    routed_scaling_factor: float
    d_conv: int
    rope_theta: float
    eps: float
    attn_impl: str = "flash"
    moe_impl: Optional[str] = None
    dtype: Any = None

    def _dense(self, features, name):
        return nn.Dense(features, use_bias=False, dtype=self.dtype, name=name)

    def _norm(self, name):
        return nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name=name)

    @nn.compact
    def __call__(self, x, training: bool = False):
        del training                       # no dropout anywhere in the stack
        y = self._norm("operator_norm")(x)
        x = x + (self._conv(y) if self.kind == CONV else self._attention(y))
        y = self._norm("ffn_norm")(x)
        return x + (self._mlp(y) if self.dense else self._experts(y))

    def _conv(self, x):
        """The gated short convolution."""
        d = self.d_model
        with jax.named_scope("conv_mix"):
            b, c, u = jnp.split(self._dense(3 * d, "in_proj")(x), 3, axis=-1)
            w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                           (self.d_conv, d))
            return self._dense(d, "out_proj")(gated_short_conv(b, c, u, w))

    def _attention(self, x):
        b, l, _ = x.shape
        h, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        with jax.named_scope("attn_full"):
            qkv = self._dense((h + 2 * hk) * dh, "qkv")(x)
            q, k, v = jnp.split(qkv, [h * dh, (h + hk) * dh], axis=-1)
            q = rope(self._norm("q_norm")(q.reshape(b, l, h, dh)),
                     self.rope_theta)
            k = rope(self._norm("k_norm")(k.reshape(b, l, hk, dh)),
                     self.rope_theta)
            v = v.reshape(b, l, hk, dh)
            if self.attn_impl == "flash":
                o = flash_attention(
                    q, k, v, causal=True, scale=dh ** -0.5,
                    block_q=_FLASH_BLOCK, block_k=_FLASH_BLOCK,
                    dot_dtype=jnp.bfloat16 if q.dtype == jnp.bfloat16
                    else None)
            else:
                o = dense_diff_scores(q, k, v, None, dh ** -0.5)
            return self._dense(self.d_model, "out_proj")(
                o.reshape(b, l, h * dh))

    def _mlp(self, x):
        with jax.named_scope("mlp_dense"):
            g, u = jnp.split(self._dense(2 * self.d_ff, "gate_up")(x), 2,
                             axis=-1)
            return self._dense(self.d_model, "down")(nn.silu(g) * u)

    def _experts(self, x):
        b, l, d = x.shape
        count, f = self.held[1], self.d_expert
        z = x.reshape(b * l, d)
        with jax.named_scope("moe_router"):
            # float32 whatever the compute dtype: a score decides a
            # selection, and a selection is a step and not a rounding
            gate = self.param("gate", nn.initializers.lecun_normal(),
                              (d, self.n_experts))
            bias = self.variable("batch_stats", "expert_bias", jnp.zeros,
                                 (self.n_experts,), jnp.float32)
            routing = route(
                jnp.dot(z.astype(jnp.float32), gate,
                        precision=jax.lax.Precision.HIGHEST),
                bias.value, self.top_k, self.routed_scaling_factor)
            if self.is_mutable_collection("moe_selected"):
                # a probe's tap (benchmark/tests/moe_probe.py): the experts
                # each token selected; nothing sows it in training
                self.sow("moe_selected", "sel", routing.sel)
        w13 = self.param("experts_w13", _expert_init, (count, d, 2 * f))
        w2 = self.param("experts_w2", _expert_init, (count, f, d))
        y, full = expert_ffn(z, routing, w13, w2, self.held, self.n_experts,
                             impl=self.moe_impl)
        if self.is_mutable_collection("moe_counts") \
                and not self.is_initializing():
            with jax.named_scope("moe_router"):
                self.sow("moe_counts", "counts",
                         routing_counts(routing.sel, self.held, full),
                         reduce_fn=jnp.add,
                         init_fn=lambda: jnp.zeros((5,), jnp.int32))
        return y.reshape(b, l, d)


def _expert_init(key, shape, dtype=jnp.float32):
    """Fan-in normal a held expert: (count, in, out)."""
    return jax.random.normal(key, shape, dtype) / jnp.sqrt(shape[1])


class Lfm2Moe(nn.Module):
    layer_types: Tuple[str, ...] = published_layer_types()
    num_dense_layers: int = 2
    vocab_rows: int = 65536
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    d_ff: int = 11776
    d_expert: int = 1536
    n_experts: int = 64
    top_k: int = 4
    # the experts this chip holds of every layer's n_experts: (first, count)
    held: Tuple[int, int] = (0, 64)
    routed_scaling_factor: float = 1.0
    d_conv: int = 3
    rope_theta: float = 1e6
    eps: float = 1e-5
    attn_impl: str = "flash"
    # ops/moe.py's form: None = its kernels on a TPU, array form elsewhere
    moe_impl: Optional[str] = None
    loss_chunk: int = 1024
    remat_policy: str = "none"
    dtype: Any = None
    default_cfg: Any = None
    # the sequence task: ids in, next-token loss out (train/steps.py)
    sequence_task = True

    def setup(self):
        assert set(self.layer_types) <= {CONV, ATTENTION}, self.layer_types
        first, count = self.held
        assert 0 <= first and count > 0 and \
            first + count <= self.n_experts, (self.held, self.n_experts)
        self.embed = nn.Embed(self.vocab_rows, self.d_model,
                              embedding_init=nn.initializers.normal(0.02),
                              dtype=self.dtype)
        layer_cls = maybe_remat(_Layer, self.remat_policy)
        self.layers = [layer_cls(
            kind=kind, dense=i < self.num_dense_layers, d_model=self.d_model,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, d_ff=self.d_ff, d_expert=self.d_expert,
            n_experts=self.n_experts, top_k=self.top_k,
            held=tuple(self.held),
            routed_scaling_factor=self.routed_scaling_factor,
            d_conv=self.d_conv, rope_theta=self.rope_theta, eps=self.eps,
            attn_impl=self.attn_impl, moe_impl=self.moe_impl,
            dtype=self.dtype) for i, kind in enumerate(self.layer_types)]
        self.final_norm = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype)

    def hidden(self, ids, training: bool = False):
        """(batch, L) ids below ``vocab_rows`` -> final hidden states."""
        with jax.named_scope("embed"):
            x = self.embed(ids)
        for layer in self.layers:
            x = layer(x, training)
        return self.final_norm(x)

    @property
    def expert_layers(self) -> int:
        return max(len(self.layer_types) - self.num_dense_layers, 0)

    def attn_tiles_visited(self, seq_len: int) -> int:
        """Grid cells the attention kernels visit in one train step over one
        row of ``seq_len`` tokens (models/granite4h.py has the count's
        text).  0 where the dense path runs."""
        if self.attn_impl != "flash":
            return 0
        cells = train_tiles_visited(seq_len, self.head_dim, _FLASH_BLOCK,
                                    _FLASH_BLOCK, True)
        return self.layer_types.count(ATTENTION) * self.n_heads * cells

    def attn_bwd_layers(self, seq_len: int) -> Tuple[int, int]:
        """The attention layers by the form their backward takes over rows
        of ``seq_len`` tokens, (fused, split).  A census."""
        if self.attn_impl != "flash":
            return 0, 0
        return fused_bwd_census(self.layer_types.count(ATTENTION), seq_len,
                                self.head_dim, _FLASH_BLOCK)

    def causal_conv_layers(self, seq_len: int) -> Tuple[int, int]:
        """The conv layers by the form their causal convolution takes over
        rows of ``seq_len`` tokens, (kernels, array form).  A census."""
        return causal_conv_census(self.layer_types.count(CONV), seq_len,
                                  self.d_model)

    def moe_layers(self, tokens: int) -> Tuple[int, int]:
        """The expert layers by the form their grouped products take over
        ``tokens`` tokens a pass, (kernels, array form):
        ops/moe.py:moe_impl.  Static per shape and backend: a census."""
        if self.moe_impl is not None:
            n = self.expert_layers
            return (n, 0) if self.moe_impl == "pallas" else (0, n)
        return moe_census(self.expert_layers, tokens, self.top_k,
                          self.d_model, self.d_expert)

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
_TINY = dict(d_model=64, n_heads=4, n_kv_heads=1, head_dim=16, d_ff=96,
             d_expert=32, n_experts=8, top_k=2, held=(0, 2), loss_chunk=16)
# published layers 0, 2, 3, 4, 5: one leading dense layer, then the first
# whole period of what follows
_CUT_TYPES = tuple(published_layer_types()[i] for i in (0, 2, 3, 4, 5))


def _entry(widths):
    def fn(pretrained=False, **kwargs):
        del pretrained
        for k in ("num_classes", "in_chans", "drop_rate", "drop_path_rate",
                  "global_pool"):
            kwargs.pop(k, None)            # the classifiers' arguments
        for k, v in widths.items():
            kwargs.setdefault(k, v)
        return Lfm2Moe(**kwargs)
    return fn


def _register():
    cut = dict(layer_types=_CUT_TYPES, num_dense_layers=1)
    for name, widths, doc in (
            ("lfm2_24b_a2b", {},
             "the published 40 layers, 64 experts held, whole vocabulary"),
            ("lfm2_24b_a2b_5l", dict(cut, vocab_rows=8192, held=(0, 8)),
             "published layers 0, 2, 3, 4, 5 (a dense conv layer, then "
             "attention, conv, conv, conv with experts), experts 0-7 of 64 "
             "and 1/8 of the vocabulary rows (469M parameters): what one "
             "chip of an 8-way expert-parallel, pipelined deployment holds"),
            ("lfm2_24b_a2b_tiny", dict(cut, vocab_rows=512, **_TINY),
             "the five-layer cut at smoke-test widths (d 64, 8 experts "
             "top-2, experts 0-1 held, 512 rows)")):
        fn = _entry(widths)
        fn.__name__ = fn.__qualname__ = name
        fn.__module__ = __name__
        fn.__doc__ = f"LFM2-24B-A2B, {doc}."
        register_model(fn)


_register()
