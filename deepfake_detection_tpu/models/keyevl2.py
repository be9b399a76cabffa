"""Keye-VL-2.0-30B-A3B's language model: grouped-query attention over keys a
learned indexer selects (DeepSeek-style sparse attention) and a softmax
router over routed experts, in every layer.

Source: ``huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B`` (config.json).
``d`` = 2048, 48 layers all alike (``decoder_sparse_step`` 1, no
``mlp_only_layers``), RMSNorm with a learned scale (eps 1e-6), no bias, an
untied head.  Every layer is

    x = h + Attn(RMSNorm(h));   h' = x + MoE(RMSNorm(x))

with ``z`` the normed input of the sub-block:

* **attention**: ``q = W_q z`` (32 heads of 128), ``k = W_k z``, ``v = W_v
  z`` (4 heads of 128); q and k RMS-normalised over a head's 128 channels
  (QK-norm), then rotated (theta 1e7, rotate-half over the 128 channels:
  the release's mRoPE is plain RoPE for text, whose three position streams
  are equal); scale 1/sqrt(128);
* **indexer** on the held input ``sg(z)``: ``qi = W_iq sg(z)`` (16 heads of
  64), ``ki = LayerNorm(W_ik sg(z))`` (one head of 64), both rotated over
  all 64 channels, ``w = W_iw sg(z) / sqrt(16 * 64)``;
* **selection and sparse attention** (``ops/sparse_attention.py``): each
  query attends to the 2,048 keys ``s <= t`` of largest ``I[t, s] = sum_j
  w[t, j] relu(qi[t, j] . ki[s])`` (all of them where ``t < 2048``), one
  selection for every head; the selection is a constant to the gradient;
* **the indexer's loss** ``KL(p_t || softmax_{S_t} I[t, .])`` with ``p_t``
  the heads' mean attention weights, held: the mean over rows and layers is
  sown into the ``aux_loss`` collection, which ``train/steps.py`` adds to
  the step's loss.  With the holds, the indexer learns from it alone and
  every other parameter from the next-token loss alone;
* **MoE**: ``r = softmax(z W_r)`` over all 128 experts in float32, the top 8
  weighing ``r_e / sum of the 8``, SwiGLU experts of 768
  (``ops/moe.py:route_softmax``, ``expert_ffn``) over the selected experts
  **that this chip holds** (``held = (first, count)``).

The vocabulary may be held in part (``vocab_rows``): the embedding and the
head rows alike.  Besides the loss a layer sows its routing counts into
``moe_counts`` and its selection's census (selected pairs, (128, 128)
blocks holding one, causal blocks) into ``dsa_counts``.  What the config
does not say (QK-norm, the pairing, the indexer's LayerNorm and scaling,
the loss's weight, the layouts) is listed under ``assumed`` in
``benchmark/configs/keye_vl2_30b_a3b_4l.json``.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..losses import next_token_loss
from ..ops.moe import expert_ffn, moe_census, route_softmax, routing_counts
from ..ops import sparse_attention as dsa_ops
from ..ops.sparse_attention import select_keys, sparse_attention
from ..registry import register_model
from .helpers import maybe_remat
from .lfm2moe import _expert_init, rope

__all__ = ["KeyeVL2", "indexer_input", "indexer_scale", "qk_normed"]


def indexer_scale(heads: int, dim: int) -> float:
    """The head weights' factor: one over the root of heads times width."""
    return (heads * dim) ** -0.5


def indexer_input(z):
    """What the indexer reads: the normed input, held (no gradient passes
    back through it)."""
    return jax.lax.stop_gradient(z)


def qk_normed(x, norm):
    """A head's query or key after its RMSNorm."""
    return norm(x)


class _Layer(nn.Module):
    """One layer: sparse attention with its indexer, then the routed
    experts held here."""
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    index_topk: int
    n_layers: int
    d_expert: int
    n_experts: int
    top_k: int
    held: Tuple[int, int]
    rope_theta: float
    eps: float
    attn_impl: str = "flash"
    dsa_impl: Optional[str] = None
    moe_impl: Optional[str] = None
    dtype: Any = None

    def _dense(self, features, name):
        return nn.Dense(features, use_bias=False, dtype=self.dtype, name=name)

    def _norm(self, name):
        return nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name=name)

    @nn.compact
    def __call__(self, x, training: bool = False):
        del training                       # no dropout anywhere in the stack
        x = x + self._attention(self._norm("input_layernorm")(x))
        return x + self._experts(self._norm("post_attention_layernorm")(x))

    def _impl(self):
        return self.dsa_impl or (None if self.attn_impl == "flash" else "xla")

    def _attention(self, z):
        b, l, _ = z.shape
        h, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        with jax.named_scope("attn_proj"):
            q = self._dense(h * dh, "q_proj")(z).reshape(b, l, h, dh)
            k = self._dense(hk * dh, "k_proj")(z).reshape(b, l, hk, dh)
            v = self._dense(hk * dh, "v_proj")(z).reshape(b, l, hk, dh)
            q = rope(qk_normed(q, self._norm("q_norm")), self.rope_theta)
            k = rope(qk_normed(k, self._norm("k_norm")), self.rope_theta)
        with jax.named_scope("dsa_index"):
            zi = indexer_input(z)
            nj, e = self.index_heads, self.index_dim
            qi = rope(self._dense(nj * e, "index_q")(zi).reshape(b, l, nj, e),
                      self.rope_theta)
            ki = nn.LayerNorm(epsilon=self.eps, dtype=self.dtype,
                              name="index_k_norm")(
                self._dense(e, "index_k")(zi))
            ki = rope(ki[:, :, None, :], self.rope_theta)[:, :, 0]
            w = self._dense(nj, "index_w")(zi).astype(jnp.float32) \
                * indexer_scale(nj, e)
        # one form for the selection and the attention: both make the scores
        impl = self._impl() or dsa_ops.sparse_impl(l, dh)
        with jax.named_scope("dsa_select"):
            sel = select_keys(qi, ki, w, self.index_topk, impl=impl)
        with jax.named_scope("attn_sparse"):
            o, kl = sparse_attention(q, k, v, qi, ki, w, sel,
                                     scale=dh ** -0.5, impl=impl)
        if self.is_mutable_collection("aux_loss") \
                and not self.is_initializing():
            with jax.named_scope("dsa_kl"):
                # this layer's share of the mean over rows and layers
                self.sow("aux_loss", "dsa_kl", jnp.mean(kl) / self.n_layers,
                         reduce_fn=jnp.add,
                         init_fn=lambda: jnp.zeros((), jnp.float32))
        if self.is_mutable_collection("dsa_counts") \
                and not self.is_initializing():
            with jax.named_scope("dsa_select"):
                self.sow("dsa_counts", "counts", sel.counts,
                         reduce_fn=jnp.add,
                         init_fn=lambda: jnp.zeros((3,), jnp.int32))
        with jax.named_scope("attn_proj"):
            return self._dense(self.d_model, "o_proj")(o.reshape(b, l, h * dh))

    def _experts(self, x):
        b, l, d = x.shape
        count, f = self.held[1], self.d_expert
        z = x.reshape(b * l, d)
        with jax.named_scope("moe_router"):
            # float32 whatever the compute dtype: a score decides a
            # selection, and a selection is a step and not a rounding
            gate = self.param("gate", nn.initializers.lecun_normal(),
                              (d, self.n_experts))
            routing = route_softmax(
                jnp.dot(z.astype(jnp.float32), gate,
                        precision=jax.lax.Precision.HIGHEST), self.top_k)
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


class KeyeVL2(nn.Module):
    n_layers: int = 48
    vocab_rows: int = 151936
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    d_expert: int = 768
    n_experts: int = 128
    top_k: int = 8
    # the experts this chip holds of every layer's n_experts: (first, count)
    held: Tuple[int, int] = (0, 128)
    rope_theta: float = 1e7
    eps: float = 1e-6
    attn_impl: str = "flash"
    # ops/sparse_attention.py's form: None = its kernels on a TPU where the
    # shapes fit, the array form elsewhere
    dsa_impl: Optional[str] = None
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
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            index_heads=self.index_heads, index_dim=self.index_dim,
            index_topk=self.index_topk, n_layers=self.n_layers,
            d_expert=self.d_expert, n_experts=self.n_experts,
            top_k=self.top_k, held=tuple(self.held),
            rope_theta=self.rope_theta, eps=self.eps,
            attn_impl=self.attn_impl, dsa_impl=self.dsa_impl,
            moe_impl=self.moe_impl, dtype=self.dtype)
            for _ in range(self.n_layers)]
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

    def dsa_layers(self) -> int:
        """The layers with learned sparse attention: every one.  A
        census."""
        return self.n_layers

    def _dsa_kernels(self, seq_len: int) -> bool:
        """Whether the sparse attention takes its kernels over a row of
        ``seq_len`` tokens (else the array form)."""
        impl = self.dsa_impl or (None if self.attn_impl == "flash" else "xla")
        return (impl or dsa_ops.sparse_impl(seq_len, self.head_dim)) \
            == "pallas"

    def dsa_fwd_bits_layers(self, seq_len: int) -> int:
        """The layers whose attention forward reads the selection kernel's
        bits (``ops/sparse_attention.py:Selection.bits``) over a row of
        ``seq_len`` tokens: every one on the kernels, none under the array
        form.  A census."""
        return self.n_layers if self._dsa_kernels(seq_len) else 0

    def attn_tiles_visited(self, seq_len: int) -> int:
        """Grid cells the sparse-attention kernels visit in one train step
        over one row of ``seq_len`` tokens (a cell runs every head): the
        forward's, the loss's and the one backward's, and the selection's
        query tiles (``ops/sparse_attention.py:train_cells``); 0 where the
        array form runs."""
        if not self._dsa_kernels(seq_len):
            return 0
        return self.n_layers * dsa_ops.train_cells(seq_len)

    def moe_layers(self, tokens: int) -> Tuple[int, int]:
        """The expert layers by the form their grouped products take over
        ``tokens`` tokens a pass, (kernels, array form).  A census."""
        if self.moe_impl is not None:
            n = self.n_layers
            return (n, 0) if self.moe_impl == "pallas" else (0, n)
        return moe_census(self.n_layers, tokens, self.top_k, self.d_model,
                          self.d_expert)

    def __call__(self, ids, training: bool = False):
        """Logits over the rows held, (batch, L, vocab_rows), float32."""
        x = self.hidden(ids, training)
        return jnp.dot(x, self.lm_head.T.astype(x.dtype),
                       preferred_element_type=jnp.float32)

    def sequence_loss(self, ids, targets, training: bool = False,
                      weight=None):
        """(mean next-token cross-entropy, token accuracy in percent) over
        the positions whose target is not negative, with the logits made a
        chunk of positions at a time.  The indexer's loss is sown apart."""
        x = self.hidden(ids, training)
        with jax.named_scope("lm_head_loss"):
            return next_token_loss(x, self.lm_head, targets,
                                   chunk=self.loss_chunk, weight=weight)


# the smoke-test size: every mechanism at widths a CPU test can afford
_TINY = dict(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             index_heads=3, index_dim=8, index_topk=16, d_expert=32,
             n_experts=16, top_k=4, held=(0, 2), loss_chunk=16)


def _entry(widths):
    def fn(pretrained=False, **kwargs):
        del pretrained
        for k in ("num_classes", "in_chans", "drop_rate", "drop_path_rate",
                  "global_pool"):
            kwargs.pop(k, None)            # the classifiers' arguments
        for k, v in widths.items():
            kwargs.setdefault(k, v)
        return KeyeVL2(**kwargs)
    return fn


def _register():
    for name, widths, doc in (
            ("keye_vl2_30b_a3b", {},
             "the published 48 layers of the language model, 128 experts "
             "held, whole vocabulary"),
            ("keye_vl2_30b_a3b_4l", dict(n_layers=4, vocab_rows=18992,
                                         held=(0, 16)),
             "published layers 0-3, experts 0-15 of 128 and 1/8 of the "
             "vocabulary rows (465M parameters): what one chip of an 8-way "
             "expert-parallel, pipelined deployment holds"),
            ("keye_vl2_tiny", dict(vocab_rows=512, **_TINY),
             "the four-layer cut at smoke-test widths (d 64, 16 experts "
             "top-4, experts 0-1 held, top-16 keys, 512 rows)")):
        fn = _entry(widths)
        fn.__name__ = fn.__qualname__ = name
        fn.__module__ = __name__
        fn.__doc__ = f"Keye-VL-2.0-30B-A3B, {doc}."
        register_model(fn)


_register()
