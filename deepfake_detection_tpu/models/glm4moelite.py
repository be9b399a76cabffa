"""GLM-4.7-Flash: multi-head latent attention (MLA) in every layer, a leading
dense SwiGLU layer, then a shared expert beside routed experts.

Source: ``huggingface.co/zai-org/GLM-4.7-Flash`` (config.json,
``model_type`` ``glm4_moe_lite``).  ``d`` = 2048, 47 layers, RMSNorm with a
learned scale (eps 1e-5), no bias anywhere, an untied head.  Every layer is

    h = x + MLA(RMSNorm_in(x));   x' = h + FFN(RMSNorm_post(h))

**MLA** (20 heads; ``q_lora_rank`` 768, ``kv_lora_rank`` 512, per head 192
channels without position and 64 rotated, values of 256):

* ``c_q = RMSNorm_qa(x W_qa)`` (768); ``q = c_q W_qb``, a head ``[q_n (192)
  | q_r (64)]``;
* ``[c_kv | k_r] = x W_kva`` (512 | 64); ``[k_n | v] = RMSNorm_kva(c_kv)
  W_kvb``, a head ``[k_n (192) | v (256)]``;
* ``q_r`` and ``k_r`` are rotated (theta 1e6, rotate-half pairs, positions
  0..L-1 a row); ``k_r`` is **one** head that every query head shares;
* ``s_h(t, u) = (q_n,h . k_n,h + q_r,h . k_r) / sqrt(256)``, causal softmax,
  ``o = concat_h(P_h v_h) W_o`` (5120 -> 2048).

The flash kernels take a head's key as ``[k_n,h | k_r]``: the two terms of
the score are one product of 256 channels, and 1/16 folds into q.

**FFN** of the first ``first_k_dense`` layers: SwiGLU of width 10240.
Otherwise ``SwiGLU_1536^shared(z) + sum_e w_e SwiGLU_1536^e(z)``: the shared
expert on every token beside the routed experts of ``ops/moe.py``,
``s = sigmoid(z W_g)`` over all 64 in float32, the top 4 of ``s +
expert_bias`` selected, weighing ``s_e / (sum of the selected s + 1e-20) *
1.8``, over the selected experts **that this chip holds** (``held = (first,
count)``).  ``expert_bias`` is a buffer outside the optimizer (the
``batch_stats`` collection), constant in training, as in
``models/lfm2moe.py``.

The published model has one multi-token-prediction layer after layer 46;
it is not built here (ROADMAP M16).  What the published config does not say
(the pairing, the layouts) is listed under ``assumed`` in
``benchmark/configs/glm47_flash_5l.json``.

The vocabulary may be held in part (``vocab_rows``): the embedding and the
head rows alike.  A layer that routes sows its counts into the
``moe_counts`` collection, which ``train/steps.py`` sums into the step's
metrics.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..losses import next_token_loss
from ..ops.flash_attention import (flash_attention, fused_bwd_census,
                                   train_tiles_visited)
from ..ops.moe import expert_ffn, moe_census, route, routing_counts
from ..registry import register_model
from .helpers import maybe_remat
from .lfm2moe import _expert_init, rope
# the plain causal attention is the SambaY family's
from .phi4flash import dense_diff_scores

__all__ = ["Glm4MoeLite", "latent_keys", "mla_scale"]

# The attention kernels' block (q and k alike): the other sequence cells'
_FLASH_BLOCK = 1024
# the weights' normalisation over the selected experts (the release's)
_NORM_EPS = 1e-20


def mla_scale(nope_dim: int, rope_dim: int) -> float:
    """The softmax scale: one over the root of the width a score sums,
    both terms of it."""
    return (nope_dim + rope_dim) ** -0.5


def latent_keys(k_n, k_r, theta: float):
    """A head's key ``[k_n,h | rotated k_r]``: ``k_n`` (batch, L, heads,
    192), ``k_r`` (batch, L, 64) the one rotary key every head shares."""
    k_r = rope(k_r[:, :, None, :], theta)
    return jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r, k_n.shape[:3] + k_r.shape[3:])], -1)


def kv_latent(c_kv, norm):
    """The key / value latent as the up projection reads it: normalised."""
    return norm(c_kv)


def moe_sum(routed, shared):
    """The expert layer's output: the routed experts' part and the shared
    expert's."""
    return routed + shared


class _Layer(nn.Module):
    """One layer: latent attention, then the dense MLP (``dense``) or the
    shared expert and the routed experts held here."""
    dense: bool
    d_model: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int
    d_expert: int
    n_shared_experts: int
    n_experts: int
    top_k: int
    held: Tuple[int, int]
    routed_scaling_factor: float
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
        x = x + self._mla(self._norm("input_layernorm")(x))
        y = self._norm("post_attention_layernorm")(x)
        return x + (self._swiglu(y, self.d_ff, "") if self.dense
                    else self._experts(y))

    def _mla(self, x):
        b, l, _ = x.shape
        h, dn, dr, dv = (self.n_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim, self.v_head_dim)
        with jax.named_scope("mla_proj"):
            c_q = self._norm("q_a_norm")(
                self._dense(self.q_lora_rank, "q_a_proj")(x))
            q = self._dense(h * (dn + dr), "q_b_proj")(c_q).reshape(
                b, l, h, dn + dr)
            c_kv, k_r = jnp.split(
                self._dense(self.kv_lora_rank + dr, "kv_a_proj")(x),
                [self.kv_lora_rank], axis=-1)
            kv = self._dense(h * (dn + dv), "kv_b_proj")(
                kv_latent(c_kv, self._norm("kv_a_norm"))).reshape(
                    b, l, h, dn + dv)
            k_n, v = jnp.split(kv, [dn], axis=-1)
            q = jnp.concatenate([q[..., :dn],
                                 rope(q[..., dn:], self.rope_theta)], -1)
            k = latent_keys(k_n, k_r, self.rope_theta)
        scale = mla_scale(dn, dr)
        with jax.named_scope("attn_latent"):
            if self.attn_impl == "flash":
                o = flash_attention(
                    q, k, v, causal=True, scale=scale,
                    block_q=_FLASH_BLOCK, block_k=_FLASH_BLOCK,
                    dot_dtype=jnp.bfloat16 if q.dtype == jnp.bfloat16
                    else None)
            else:
                o = dense_diff_scores(q, k, v, None, scale)
        with jax.named_scope("mla_proj"):
            return self._dense(self.d_model, "o_proj")(o.reshape(b, l, h * dv))

    def _swiglu(self, x, width, prefix):
        """``[w1 | w3]`` in one kernel, then the down projection."""
        with jax.named_scope("moe_shared" if prefix else "mlp_dense"):
            g, u = jnp.split(self._dense(2 * width, prefix + "gate_up")(x), 2,
                             axis=-1)
            return self._dense(self.d_model, prefix + "down")(nn.silu(g) * u)

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
                bias.value, self.top_k, self.routed_scaling_factor,
                norm_eps=_NORM_EPS)
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
        shared = self._swiglu(x, self.n_shared_experts * f, "shared_")
        return moe_sum(y.reshape(b, l, d), shared)


class Glm4MoeLite(nn.Module):
    n_layers: int = 47
    first_k_dense: int = 1
    vocab_rows: int = 154880
    d_model: int = 2048
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    d_ff: int = 10240
    d_expert: int = 1536
    n_shared_experts: int = 1
    n_experts: int = 64
    top_k: int = 4
    # the experts this chip holds of every layer's n_experts: (first, count)
    held: Tuple[int, int] = (0, 64)
    routed_scaling_factor: float = 1.8
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
        first, count = self.held
        assert 0 <= first and count > 0 and \
            first + count <= self.n_experts, (self.held, self.n_experts)
        self.embed = nn.Embed(self.vocab_rows, self.d_model,
                              embedding_init=nn.initializers.normal(0.02),
                              dtype=self.dtype)
        layer_cls = maybe_remat(_Layer, self.remat_policy)
        self.layers = [layer_cls(
            dense=i < self.first_k_dense, d_model=self.d_model,
            n_heads=self.n_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, d_ff=self.d_ff,
            d_expert=self.d_expert, n_shared_experts=self.n_shared_experts,
            n_experts=self.n_experts, top_k=self.top_k,
            held=tuple(self.held),
            routed_scaling_factor=self.routed_scaling_factor,
            rope_theta=self.rope_theta, eps=self.eps,
            attn_impl=self.attn_impl, moe_impl=self.moe_impl,
            dtype=self.dtype) for i in range(self.n_layers)]
        self.final_norm = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype)
        # untied: the head's rows are a parameter of their own
        self.lm_head = self.param("lm_head", nn.initializers.normal(0.02),
                                  (self.vocab_rows, self.d_model))

    def hidden(self, ids, training: bool = False):
        """(batch, L) ids below ``vocab_rows`` -> final hidden states."""
        with jax.named_scope("embed"):
            x = self.embed(ids)
        for layer in self.layers:
            x = layer(x, training)
        return self.final_norm(x)

    @property
    def expert_layers(self) -> int:
        return max(self.n_layers - self.first_k_dense, 0)

    def mla_layers(self) -> int:
        """The layers with latent attention: every one.  A census."""
        return self.n_layers

    def attn_tiles_visited(self, seq_len: int) -> int:
        """Grid cells the attention kernels visit in one train step over one
        row of ``seq_len`` tokens (models/granite4h.py has the count's
        text).  0 where the dense path runs."""
        if self.attn_impl != "flash":
            return 0
        cells = train_tiles_visited(
            seq_len, self.qk_nope_head_dim + self.qk_rope_head_dim,
            _FLASH_BLOCK, _FLASH_BLOCK, True)
        return self.n_layers * self.n_heads * cells

    def attn_bwd_layers(self, seq_len: int) -> Tuple[int, int]:
        """The attention layers by the form their backward takes over rows
        of ``seq_len`` tokens, (fused, split).  A census."""
        if self.attn_impl != "flash":
            return 0, 0
        return fused_bwd_census(
            self.n_layers, seq_len,
            self.qk_nope_head_dim + self.qk_rope_head_dim, _FLASH_BLOCK)

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
        return jnp.dot(x, self.lm_head.T.astype(x.dtype),
                       preferred_element_type=jnp.float32)

    def sequence_loss(self, ids, targets, training: bool = False,
                      weight=None):
        """(mean next-token cross-entropy, token accuracy in percent) over
        the positions whose target is not negative, with the logits made a
        chunk of positions at a time."""
        x = self.hidden(ids, training)
        with jax.named_scope("lm_head_loss"):
            return next_token_loss(x, self.lm_head, targets,
                                   chunk=self.loss_chunk, weight=weight)


# the smoke-test size: every mechanism at widths a CPU test can afford
_TINY = dict(n_layers=5, d_model=64, n_heads=4, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
             v_head_dim=16, d_ff=96, d_expert=32, n_experts=8, top_k=2,
             held=(0, 2), loss_chunk=16)


def _entry(widths):
    def fn(pretrained=False, **kwargs):
        del pretrained
        for k in ("num_classes", "in_chans", "drop_rate", "drop_path_rate",
                  "global_pool"):
            kwargs.pop(k, None)            # the classifiers' arguments
        for k, v in widths.items():
            kwargs.setdefault(k, v)
        return Glm4MoeLite(**kwargs)
    return fn


def _register():
    for name, widths, doc in (
            ("glm47_flash", {},
             "the published 47 layers (no multi-token-prediction layer), 64 "
             "experts held, whole vocabulary"),
            ("glm47_flash_5l", dict(n_layers=5, vocab_rows=19360,
                                    held=(0, 8)),
             "published layers 0-4 (the dense layer, then four expert "
             "layers), experts 0-7 of 64 and 1/8 of the vocabulary rows "
             "(591M parameters): what one chip of an 8-way expert-parallel, "
             "pipelined deployment holds"),
            ("glm47_flash_tiny", dict(vocab_rows=512, **_TINY),
             "the five-layer cut at smoke-test widths (d 64, 8 experts "
             "top-2, experts 0-1 held, 512 rows)")):
        fn = _entry(widths)
        fn.__name__ = fn.__qualname__ = name
        fn.__module__ = __name__
        fn.__doc__ = f"GLM-4.7-Flash, {doc}."
        register_model(fn)


_register()
