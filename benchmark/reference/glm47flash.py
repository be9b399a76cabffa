"""Plain reference of the GLM-4.7-Flash stack (multi-head latent attention
in every layer, a leading dense SwiGLU layer, then a shared expert beside a
sigmoid router with a selection bias over routed experts held in part):
forward, next-token loss and gradients.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision (every
product names it, and the entry points run under
``jax.default_matmul_precision("highest")``), written from the layer
equations (``models/glm4moelite.py``'s text has them) and the sizes the
configuration file states.  It imports nothing of the program under test and calls no kernel
(the control's rounding and the ``product`` it wraps come from
``reference/phi4flash.py``, the norm, the rotation, the SwiGLU and the head's
chunked loss from ``reference/lfm2moe.py``).

* Latent attention keeps the two terms of a score apart: ``q_n,h . k_n,h``
  over a head's own 192 channels plus ``q_r,h . k_r`` over the 64 rotated
  channels of the one key head every query head shares, over
  ``sqrt(192 + 64)``.  It goes by blocks of queries against all keys, each
  block made again in the backward pass.
* The expert layer is the plainest thing that is right: **every held expert
  is applied to every token** and its output multiplied by a weight that is
  0 where the token did not select it (no sort, no gather, no capacity); the
  shared expert runs once on every token.  It is given the same ``held`` as
  the program (``n_routed_experts`` experts from ``held_first`` of the
  published ``num_experts_published``), routes over all the published
  experts and normalises over all the selected ones.
* It runs layer by layer and row by row: one jitted forward and one jitted
  vector-Jacobian product per kind of layer, the inputs of each layer kept,
  gradients averaged over the rows.

Departures from the published description, all of layout and none of value:

* rotate-half pairing over the 64 rotary channels (channel ``i`` with ``i +
  32``); the release pairs neighbouring channels, which is the same
  mathematics up to a fixed permutation of ``q_b_proj``'s and
  ``kv_a_proj``'s rotary columns;
* an MLP's, the shared expert's or an expert's first product is one kernel
  ``[w1 | w3]``; the held experts are stacked: ``experts_w13`` (held, d, 2 x
  1536), ``experts_w2`` (held, 1536, d);
* the multi-token-prediction layer is not built (the configuration's
  ``reduced``);
* the weight-decay mask, the clipping and Adam's bias correction live in
  ``optim_adamw.py``;
* a target of -1 marks the last position of a row (nothing follows it).

``expert_bias`` is a buffer and no parameter: the second tree of
``param_shapes``.  ``drivers/train_seq.py`` hands ``loss_and_grads`` no
second tree, so the buffer is a constant of the configuration, as
``reference/lfm2moe.py``'s is: ``init_leaf`` draws it N(0, 0.01) from a key
fixed here and the layer's index, and ``loss_and_grads`` draws the same where
it is handed none.

``quant`` is the control's hook, as in ``reference/lfm2moe.py``: ``"fp8"``
rounds the operands of every product the program makes in bfloat16 to
float8 and what flows between them to bfloat16; the router stays float32.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.lfm2moe import head_loss, rms_norm, rotate, swiglu
from benchmark.reference.phi4flash import _ACT, HIGHEST, product

Q_BLOCK = 256          # queries a block of attention takes
NORM_EPS = 1e-20       # in the selected weights' normalisation (the release's)
BIAS_STD, BIAS_KEY = 0.01, 0x676c6d34


def _highest(fn):
    """Trace and run ``fn`` with every matrix product at full float32."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------

def model_spec(config: Dict[str, Any]) -> Dict[str, Any]:
    """Sizes from the configuration file's published keys."""
    spec = {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "dv": int(config["v_head_dim"]),
        "ff": int(config["intermediate_size"]),
        "f": int(config["moe_intermediate_size"]),
        "shared": int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        "eps": float(config["rms_norm_eps"]),
        "experts": int(config.get("num_experts_published",
                                  config["n_routed_experts"])),
        "held": (int(config.get("held_first", 0)),
                 int(config["n_routed_experts"])),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "theta": float(config["rope_theta"]),
        "layers": int(config["num_hidden_layers"]),
        "dense": int(config["first_k_dense_replace"]),
        "rows": int(config["vocab_size"]),
    }
    spec["num_classes"] = spec["rows"]
    assert config["norm_topk_prob"] and config["topk_method"] == "noaux_tc"
    assert config["n_group"] == config["topk_group"] == 1
    assert not config["tie_word_embeddings"] and not config["attention_bias"]
    assert config["hidden_act"] == "silu" and config["rope_scaling"] is None
    return spec


def schedule(spec) -> Tuple[bool, ...]:
    """Dense FFN? a layer."""
    return tuple(i < spec["dense"] for i in range(spec["layers"]))


def forward_counts(config: Dict[str, Any]) -> Dict[str, float]:
    """Operations and bytes of ONE row of the configuration's length."""
    from benchmark.lib import flops_mla
    spec = model_spec(config)
    return flops_mla.counts_for(spec, schedule(spec),
                                int(config["train"]["seq_len"]))


def _layer_shapes(dense: bool, s) -> Dict[str, Any]:
    d, h = s["d"], s["heads"]
    out = {"input_layernorm": {"scale": (d,)},
           "post_attention_layernorm": {"scale": (d,)},
           "q_a_proj": {"kernel": (d, s["q_rank"])},
           "q_a_norm": {"scale": (s["q_rank"],)},
           "q_b_proj": {"kernel": (s["q_rank"], h * (s["nope"] + s["rope"]))},
           "kv_a_proj": {"kernel": (d, s["kv_rank"] + s["rope"])},
           "kv_a_norm": {"scale": (s["kv_rank"],)},
           "kv_b_proj": {"kernel": (s["kv_rank"], h * (s["nope"] + s["dv"]))},
           "o_proj": {"kernel": (h * s["dv"], d)}}
    if dense:
        out.update({"gate_up": {"kernel": (d, 2 * s["ff"])},
                    "down": {"kernel": (s["ff"], d)}})
    else:
        held = s["held"][1]
        out.update({"gate": (d, s["experts"]),
                    "experts_w13": (held, d, 2 * s["f"]),
                    "experts_w2": (held, s["f"], d),
                    "shared_gate_up": {"kernel": (d, 2 * s["shared"])},
                    "shared_down": {"kernel": (s["shared"], d)}})
    return out


def param_shapes(spec):
    """(parameters, buffers): the second tree holds each expert layer's
    selection bias, which no optimizer sees."""
    shapes = {"embed": {"embedding": (spec["rows"], spec["d"])},
              "lm_head": (spec["rows"], spec["d"]),
              "final_norm": {"scale": (spec["d"],)}}
    stats = {}
    for i, dense in enumerate(schedule(spec)):
        shapes[f"layers_{i}"] = _layer_shapes(dense, spec)
        if not dense:
            stats[f"layers_{i}"] = {"expert_bias": (spec["experts"],)}
    return shapes, stats


def expert_bias(layer: int, experts: int):
    """The selection bias of one layer: N(0, 0.01), from a key fixed here.
    Drawn on the host: the same bits inside a jitted initialiser and
    outside."""
    rng = np.random.default_rng([BIAS_KEY, layer])
    return jnp.asarray(BIAS_STD * rng.standard_normal(experts), jnp.float32)


def default_stats(spec):
    return {name: {"expert_bias": expert_bias(int(name.split("_")[1]),
                                              spec["experts"])}
            for name in param_shapes(spec)[1]}


def init_leaf(key, path: Tuple[str, ...], shape):
    """Seeded weights in sane ranges: fan-in kernels (an expert's fan-in is
    its second-to-last axis), norm scales around 1, embedding and head std
    0.02, each branch's last projection times 1 / sqrt(2 x 5 layers); the
    selection bias from its own fixed key."""
    name = path[-1]
    if name == "expert_bias":
        return expert_bias(int(path[-2].split("_")[1]), shape[0])
    n = jax.random.normal(key, shape, jnp.float32)
    if name in ("embedding", "lm_head"):
        return 0.02 * n
    if name == "scale":
        return 1.0 + 0.1 * n
    fan_in = shape[-2] if len(shape) > 1 else shape[0]
    last = name == "experts_w2" or (path[-1] == "kernel" and path[-2] in (
        "o_proj", "down", "shared_down"))
    return n / math.sqrt(fan_in) * (1 / math.sqrt(10.0) if last else 1.0)


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def latent_attention(p, x, s, quant):
    """Causal multi-head latent attention of one row x (L, d)."""
    l = x.shape[0]
    h, dn, dr, dv = s["heads"], s["nope"], s["rope"], s["dv"]
    act = _ACT[quant]
    c_q = act(rms_norm(product("ld,dr->lr", x, p["q_a_proj"]["kernel"],
                               quant), p["q_a_norm"]["scale"], s["eps"]))
    q = product("lr,re->le", c_q, p["q_b_proj"]["kernel"], quant).reshape(
        l, h, dn + dr)
    latent = product("ld,de->le", x, p["kv_a_proj"]["kernel"], quant)
    c_kv = act(rms_norm(latent[:, :s["kv_rank"]], p["kv_a_norm"]["scale"],
                        s["eps"]))
    kv = product("lc,ce->le", c_kv, p["kv_b_proj"]["kernel"],
                 quant).reshape(l, h, dn + dv)
    q_n, q_r = act(q[..., :dn]), act(rotate(q[..., dn:], s["theta"]))
    k_n, v = act(kv[..., :dn]), act(kv[..., dn:])
    # the one rotary key head, shared by every query head: (L, 64)
    k_r = act(rotate(latent[:, None, s["kv_rank"]:], s["theta"])[:, 0])
    pos = jnp.arange(l)

    @jax.checkpoint
    def block(args):
        qn, qr, tb = args                          # (Bq, H, ·), (Bq,)
        sc = (product("qhd,khd->hqk", qn, k_n, quant)
              + product("qhd,kd->hqk", qr, k_r, quant)) / math.sqrt(dn + dr)
        ok = pos[None, :] <= tb[:, None]
        a = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        return product("hqk,khe->qhe", act(a), v, quant)

    bq = min(Q_BLOCK, l)
    pad = -l % bq
    qs = [jnp.pad(t, ((0, pad), (0, 0), (0, 0))).reshape(-1, bq, h,
                                                         t.shape[-1])
          for t in (q_n, q_r)]
    tp = jnp.pad(pos, (0, pad), constant_values=l - 1).reshape(-1, bq)
    o = jax.lax.map(block, (qs[0], qs[1], tp))
    o = act(o.reshape(-1, h * dv)[:l])
    return product("le,ed->ld", o, p["o_proj"]["kernel"], quant)


def selected(p, bias, x, s):
    """(float32 scores (L, experts), the top-k experts (L, k) of score +
    bias): the bias selects."""
    score = jax.nn.sigmoid(jnp.einsum("ld,de->le", x, p["gate"],
                                      precision=HIGHEST))
    return score, jax.lax.top_k(score + bias, s["top_k"])[1]


def routing_weights(p, bias, x, s):
    """(L, experts) float32: a selected expert's weight, 0 elsewhere: the
    score without the bias, normalised over the selected, times the
    routed scaling factor."""
    score, sel = selected(p, bias, x, s)
    chosen = jnp.sum(jax.nn.one_hot(sel, s["experts"], dtype=jnp.float32),
                     axis=1)
    picked = score * chosen
    return picked / (jnp.sum(picked, axis=1, keepdims=True) + NORM_EPS) \
        * s["scale"]


def experts(p, bias, x, s, quant):
    """The shared expert once, and every held expert on every token times
    the token's weight for it."""
    first, held = s["held"]
    w = routing_weights(p, bias, x, s)
    xq = _ACT[quant](x)

    @jax.checkpoint
    def one(e):
        return w[:, first + e, None] * swiglu(
            xq, p["experts_w13"][e], p["experts_w2"][e], s["f"], quant)
    shared = swiglu(xq, p["shared_gate_up"]["kernel"],
                    p["shared_down"]["kernel"], s["shared"], quant)
    return shared + sum(one(e) for e in range(held))


def ffn_input(p, x, s, quant=None):
    """(the residual stream after attention, its RMSNorm: what the FFN or
    the router reads) of one layer on one row: x (L, d)."""
    y = _ACT[quant](rms_norm(x, p["input_layernorm"]["scale"], s["eps"]))
    x = x + latent_attention(p, y, s, quant)
    return x, rms_norm(x, p["post_attention_layernorm"]["scale"], s["eps"])


def layer_forward(p, bias, x, s, dense, quant=None):
    """One layer on one row: x (L, d)."""
    x, y = ffn_input(p, x, s, quant)
    if dense:
        return x + swiglu(_ACT[quant](y), p["gate_up"]["kernel"],
                          p["down"]["kernel"], s["ff"], quant)
    return x + experts(p, bias, y, s, quant)


# ---------------------------------------------------------------------------
# layer by layer
# ---------------------------------------------------------------------------

def _skey(spec):
    return tuple(sorted(spec.items()))


@functools.lru_cache(maxsize=None)
def _jitted_layer(dense: bool, skey, quant):
    spec = dict(skey)

    def fwd(p, bias, x):
        return layer_forward(p, bias, x, spec, dense, quant)

    def bwd(p, bias, x, dx):
        return jax.vjp(lambda p_, x_: fwd(p_, bias, x_), p, x)[1](dx)
    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _jitted_head(skey, quant):
    spec = dict(skey)

    def both(w, norm, x, t):
        return jax.value_and_grad(
            lambda w_, n_, x_: head_loss(w_, n_, x_, t, spec, quant),
            argnums=(0, 1, 2))(w, norm, x)
    return jax.jit(both)


def _biases(stats, spec):
    """Each layer's selection bias (None for a dense layer), from the
    buffers handed in or, where there are none, the configuration's."""
    stats = stats if stats else default_stats(spec)
    return [None if dense else stats[f"layers_{i}"]["expert_bias"]
            for i, dense in enumerate(schedule(spec))]


def _row_forward(params, biases, ids, spec, quant=None, keep=None):
    x = params["embed"]["embedding"][ids]
    skey = _skey(spec)
    for i, dense in enumerate(schedule(spec)):
        if keep is not None:
            keep.append(x)
        x = _jitted_layer(dense, skey, quant)[0](
            params[f"layers_{i}"], biases[i], x)
    return x


@_highest
def inference_forward(params, stats, ids, spec):
    """Logits (rows, L, vocabulary rows held) of the whole stack."""
    biases = _biases(stats, spec)
    outs = []
    for row in ids:
        x = rms_norm(_row_forward(params, biases, row, spec),
                     params["final_norm"]["scale"], spec["eps"])
        outs.append(jnp.einsum("ld,vd->lv", x, params["lm_head"],
                               precision=HIGHEST))
    return jnp.stack(outs)


def prologue(ids, step_index: int, aug: Dict[str, Any], seed: int):
    """The step is fed the ids as the host loader yields them."""
    return ids


def _row_loss_and_grads(params, biases, ids, targets, spec, quant):
    skey = _skey(spec)
    keep = []
    x = _row_forward(params, biases, ids, spec, quant, keep)
    loss, (d_head, d_norm, dx) = _jitted_head(skey, quant)(
        params["lm_head"], params["final_norm"], x, targets)
    grads = {"final_norm": d_norm, "lm_head": d_head}
    layers = schedule(spec)
    for i in reversed(range(len(layers))):
        grads[f"layers_{i}"], dx = _jitted_layer(layers[i], skey, quant)[1](
            params[f"layers_{i}"], biases[i], keep[i], dx)
        keep[i] = None
    grads["embed"] = {"embedding": jnp.zeros_like(
        params["embed"]["embedding"]).at[ids].add(dx)}
    return loss, grads


@_highest
def loss_and_grads(params, stats, x, y, spec, quant=None):
    """Loss and gradients of a batch of rows: ids ``x`` and targets ``y``
    (rows, L), the loss a mean over every position with a target; one row
    at a time, each weighed by its share of the targets."""
    biases = _biases(stats, spec)
    rows = x.shape[0]
    counts = [int(jnp.sum(y[r] >= 0)) for r in range(rows)]
    total = max(sum(counts), 1)
    loss, grads = 0.0, None
    for r in range(rows):
        l_r, g_r = _row_loss_and_grads(params, biases, x[r], y[r], spec,
                                       quant)
        w = counts[r] / total
        loss = loss + w * l_r
        g_r = jax.tree.map(lambda g: w * g, g_r)
        grads = g_r if grads is None else jax.tree.map(jnp.add, grads, g_r)
    return loss, grads, stats, None
