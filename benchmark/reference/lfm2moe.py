"""Plain reference of the LFM2 mixture-of-experts stack (gated short
convolutions with RoPE / QK-norm grouped attention among them, a sigmoid
router with a selection bias, routed SwiGLU experts held in part): forward,
next-token loss and gradients.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the layer equations (ISSUE 32 spells them out) and the sizes the
configuration file states.  It imports nothing of the program under test and
calls no kernel (the control's rounding and the ``product`` it wraps come
from ``reference/phi4flash.py``).  The expert layer is the plainest thing
that is right: **every held expert is applied to every token** and its
output multiplied by a weight that is 0 where the token did not select it:
no sort, no gather, no group, no capacity.  It is given the same ``held`` as
the program (``num_experts`` experts from ``held_first`` of the published
``num_experts_published``), routes over all the published experts and
normalises over all the selected ones.  Attention goes by blocks of queries
against all keys, the logits by chunks of positions; each is made again in
the backward pass instead of being kept.  It runs layer by layer and row by
row: one jitted forward and one jitted vector-Jacobian product per layer
kind, the inputs of each layer kept, gradients averaged over the rows, so
the whole model is never one float32 program.

Departures from the published description, all of layout and none of value:

* the attention projections are one kernel, columns ``[q | k | v]`` (32 x
  64, 8 x 64, 8 x 64); the release keeps three;
* ``in_proj``'s columns are ``[B | C | u]``, as the release has them; the
  convolution's kernel is stored (taps, channels), tap ``k`` multiplying
  position ``t - 2 + k``;
* an MLP's or an expert's first product is one kernel ``[w1 | w3]``; the
  experts held are stacked: ``experts_w13`` (held, d, 2 x 1536),
  ``experts_w2`` (held, 1536, d);
* the weight-decay mask, the clipping and Adam's bias correction live in
  ``optim_adamw.py``;
* a target of -1 marks the last position of a row (nothing follows it).

What the published config does not say is the configuration's ``assumed``:
tied embedding and a final RMSNorm, the per-head norm before the rotation,
rotate-half pairing, the 1e-6 in the weights' normalisation.

``expert_bias`` is a buffer and no parameter: the second tree of
``param_shapes``.  The accepted driver hands ``loss_and_grads`` no second
tree (``drivers/train_seq.py`` passes ``{}``), so the buffer is a constant
of the configuration: ``init_leaf`` draws it N(0, 0.01) from a key fixed
here and the layer's index, whatever the run's seed, and ``loss_and_grads``
draws the same where it is handed none.

``quant`` is the control's hook: ``None`` computes as above; ``"fp8"`` rounds
the operands of every matrix product the program makes in bfloat16
(projections, scores, values, experts, head) to float8 e4m3 scaled per
tensor, their cotangents to e5m2, and what flows between them to bfloat16:
one precision below the bfloat16 the configuration states.  The router's
product, sigmoid, selection and normalisation stay float32, as the
program's.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.phi4flash import (_ACT, HIGHEST, product, silu)

CONV, ATTENTION = "conv", "full_attention"
Q_BLOCK = 256          # queries a block of attention takes
HEAD_CHUNK = 1024      # positions a chunk of the logits takes
NORM_EPS = 1e-6        # in the selected weights' normalisation
BIAS_STD, BIAS_KEY = 0.01, 0x6c666d32


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------

def model_spec(config: Dict[str, Any]) -> Dict[str, Any]:
    """Sizes from the configuration file's published keys; the layers are
    ``layers_kept`` of ``layer_types`` (all of them where the file keeps
    every layer), the first ``num_dense_layers`` of those dense."""
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    kept = config.get("layers_kept") or range(len(config["layer_types"]))
    spec = {
        "d": d, "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]), "dh": d // heads,
        "ff": int(config["intermediate_size"]),
        "f": int(config["moe_intermediate_size"]),
        "eps": float(config["norm_eps"]),
        "taps": int(config["conv_L_cache"]),
        "experts": int(config.get("num_experts_published",
                                  config["num_experts"])),
        "held": (int(config.get("held_first", 0)),
                 int(config["num_experts"])),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "theta": float(config["rope_parameters"]["rope_theta"]),
        "kinds": tuple(config["layer_types"][i] for i in kept),
        "dense": int(config["num_dense_layers"]),
        "rows": int(config["vocab_size"]),
    }
    spec["num_classes"] = spec["rows"]
    assert len(spec["kinds"]) == int(config["num_hidden_layers"])
    assert not config["conv_bias"] and config["norm_topk_prob"] \
        and config["use_expert_bias"]
    assert set(spec["kinds"]) <= {CONV, ATTENTION}
    return spec


def schedule(spec) -> Tuple[Tuple[str, bool], ...]:
    """(mixer kind, dense FFN?) a layer."""
    return tuple((k, i < spec["dense"]) for i, k in enumerate(spec["kinds"]))


def forward_counts(config: Dict[str, Any]) -> Dict[str, float]:
    """Operations and bytes of ONE row of the configuration's length."""
    from benchmark.lib import flops_moe
    spec = model_spec(config)
    return flops_moe.counts_for(spec, schedule(spec),
                                int(config["train"]["seq_len"]))


def _layer_shapes(kind: str, dense: bool, s) -> Dict[str, Any]:
    d = s["d"]
    out = {"operator_norm": {"scale": (d,)}, "ffn_norm": {"scale": (d,)},
           "out_proj": {"kernel": (d, d)}}
    if kind == CONV:
        out.update({"in_proj": {"kernel": (d, 3 * d)},
                    "conv_kernel": (s["taps"], d)})
    else:
        out.update({
            "qkv": {"kernel": (d, (s["heads"] + 2 * s["kv_heads"])
                               * s["dh"])},
            "q_norm": {"scale": (s["dh"],)}, "k_norm": {"scale": (s["dh"],)}})
    if dense:
        out.update({"gate_up": {"kernel": (d, 2 * s["ff"])},
                    "down": {"kernel": (s["ff"], d)}})
    else:
        held = s["held"][1]
        out.update({"gate": (d, s["experts"]),
                    "experts_w13": (held, d, 2 * s["f"]),
                    "experts_w2": (held, s["f"], d)})
    return out


def param_shapes(spec):
    """(parameters, buffers): the second tree holds each expert layer's
    selection bias, which no optimizer sees."""
    shapes = {"embed": {"embedding": (spec["rows"], spec["d"])},
              "final_norm": {"scale": (spec["d"],)}}
    stats = {}
    for i, (kind, dense) in enumerate(schedule(spec)):
        shapes[f"layers_{i}"] = _layer_shapes(kind, dense, spec)
        if not dense:
            stats[f"layers_{i}"] = {"expert_bias": (spec["experts"],)}
    return shapes, stats


def expert_bias(layer: int, experts: int):
    """The selection bias of one layer: N(0, 0.01), large enough beside
    sigmoid scores that it changes selections, from a key fixed here.  Drawn
    on the host: the same bits inside a jitted initialiser and outside."""
    rng = np.random.default_rng([BIAS_KEY, layer])
    return jnp.asarray(BIAS_STD * rng.standard_normal(experts), jnp.float32)


def default_stats(spec):
    return {name: {"expert_bias": expert_bias(int(name.split("_")[1]),
                                              spec["experts"])}
            for name in param_shapes(spec)[1]}


def init_leaf(key, path: Tuple[str, ...], shape):
    """Seeded weights in sane ranges: fan-in kernels (an expert's fan-in is
    its second-to-last axis), norm scales around 1, embedding std 0.02, each
    branch's last projection times 1 / sqrt(2 x 5 layers); the selection
    bias from its own fixed key."""
    name = path[-1]
    if name == "expert_bias":
        return expert_bias(int(path[-2].split("_")[1]), shape[0])
    n = jax.random.normal(key, shape, jnp.float32)
    if name == "embedding":
        return 0.02 * n
    if name == "scale":
        return 1.0 + 0.1 * n
    fan_in = shape[-2] if len(shape) > 1 else shape[0]
    last = name == "experts_w2" or path[-2:] in (("out_proj", "kernel"),
                                                 ("down", "kernel"))
    return n / math.sqrt(fan_in) * (1 / math.sqrt(10.0) if last else 1.0)


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * scale


def rotate(x, theta):
    """x (L, heads, dh): channel i of the first half and channel i + dh / 2
    are a pair, turned by position x theta ** (-2 i / dh)."""
    l, _, dh = x.shape
    half = dh // 2
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] \
        * theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / dh)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(p, x, s, quant):
    """out_proj(C * conv(B * u)): three taps looking back, no bias."""
    d, l = s["d"], x.shape[0]
    bcu = _ACT[quant](product("ld,de->le", x, p["in_proj"]["kernel"], quant))
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    padded = jnp.pad(b * u, ((s["taps"] - 1, 0), (0, 0)))
    y = sum(padded[k:k + l] * p["conv_kernel"][k] for k in range(s["taps"]))
    return product("le,ed->ld", _ACT[quant](c * y), p["out_proj"]["kernel"],
                   quant)


def attention(p, x, s, quant):
    """Causal softmax attention, four query heads to one KV head; q and k
    RMS-normalised over the head's channels, then rotated."""
    l = x.shape[0]
    h, hk, dh = s["heads"], s["kv_heads"], s["dh"]
    qkv = _ACT[quant](product("ld,de->le", x, p["qkv"]["kernel"], quant))
    q = qkv[:, :h * dh].reshape(l, h, dh)
    k = qkv[:, h * dh:(h + hk) * dh].reshape(l, hk, dh)
    v = qkv[:, (h + hk) * dh:].reshape(l, hk, dh)
    q = _ACT[quant](rotate(rms_norm(q, p["q_norm"]["scale"], s["eps"]),
                           s["theta"]))
    k = _ACT[quant](rotate(rms_norm(k, p["k_norm"]["scale"], s["eps"]),
                           s["theta"]))
    k, v = (jnp.repeat(t, h // hk, axis=1) for t in (k, v))
    pos = jnp.arange(l)

    @jax.checkpoint
    def block(args):
        qb, tb = args                              # (Bq, H, dh), (Bq,)
        sc = product("qhd,khd->hqk", qb, k, quant) / math.sqrt(dh)
        ok = pos[None, :] <= tb[:, None]
        a = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        return product("hqk,khe->qhe", _ACT[quant](a), v, quant)

    bq = min(Q_BLOCK, l)
    pad = -l % bq
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    tp = jnp.pad(pos, (0, pad), constant_values=l - 1)
    o = jax.lax.map(block, (qp.reshape(-1, bq, h, dh), tp.reshape(-1, bq)))
    o = _ACT[quant](o.reshape(-1, h * dh)[:l])
    return product("le,ed->ld", o, p["out_proj"]["kernel"], quant)


def swiglu(x, w13, w2, width, quant):
    y = product("ld,df->lf", x, w13, quant)
    return product("lf,fd->ld", _ACT[quant](
        silu(y[:, :width]) * y[:, width:]), w2, quant)


def selected(p, bias, x, s):
    """(float32 scores (L, experts), the top-k experts (L, k) of score +
    bias): the bias selects."""
    score = jax.nn.sigmoid(jnp.einsum("ld,de->le", x, p["gate"],
                                      precision=HIGHEST))
    return score, jax.lax.top_k(score + bias, s["top_k"])[1]


def routing_weights(p, bias, x, s):
    """(L, experts) float32: a selected expert's weight, 0 elsewhere: the
    score without the bias, normalised over the selected."""
    score, sel = selected(p, bias, x, s)
    chosen = jnp.sum(jax.nn.one_hot(sel, s["experts"], dtype=jnp.float32),
                     axis=1)
    picked = score * chosen
    return picked / (jnp.sum(picked, axis=1, keepdims=True) + NORM_EPS) \
        * s["scale"]


def experts(p, bias, x, s, quant):
    """Every held expert on every token, times the token's weight for it."""
    first, held = s["held"]
    w = routing_weights(p, bias, x, s)
    xq = _ACT[quant](x)

    @jax.checkpoint
    def one(e):
        return w[:, first + e, None] * swiglu(
            xq, p["experts_w13"][e], p["experts_w2"][e], s["f"], quant)
    return sum(one(e) for e in range(held))


def ffn_input(p, x, s, kind, quant=None):
    """(the residual stream after the mixer, its RMSNorm: what the FFN or
    the router reads) of one layer on one row: x (L, d)."""
    y = _ACT[quant](rms_norm(x, p["operator_norm"]["scale"], s["eps"]))
    x = x + (short_conv(p, y, s, quant) if kind == CONV
             else attention(p, y, s, quant))
    return x, rms_norm(x, p["ffn_norm"]["scale"], s["eps"])


def layer_forward(p, bias, x, s, kind, dense, quant=None):
    """One layer on one row: x (L, d)."""
    x, y = ffn_input(p, x, s, kind, quant)
    if dense:
        return x + swiglu(_ACT[quant](y), p["gate_up"]["kernel"],
                          p["down"]["kernel"], s["ff"], quant)
    return x + experts(p, bias, y, s, quant)


def selections(params, stats, ids, spec):
    """The experts each token of one row ``ids`` (L,) selects in every
    expert layer: {layer name: (L, k) int32}."""
    biases = _biases(stats, spec)
    skey = _skey(spec)
    x = params["embed"]["embedding"][ids]
    out = {}
    for i, (kind, dense) in enumerate(schedule(spec)):
        p = params[f"layers_{i}"]
        if not dense:
            out[f"layers_{i}"] = _jitted_selected(kind, skey)(p, biases[i], x)
        x = _jitted_layer(kind, dense, skey, None)[0](p, biases[i], x)
    return out


def head_loss(p_embed, p_norm, x, targets, s, quant=None):
    """Mean next-token cross-entropy over the positions with a target."""
    x = _ACT[quant](rms_norm(x, p_norm["scale"], s["eps"]))
    l = x.shape[0]
    chunk = min(HEAD_CHUNK, l)
    pad = -l % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    tp = jnp.pad(targets, (0, pad), constant_values=-1)

    @jax.checkpoint
    def one(args):
        xc, tc = args
        logits = product("ld,vd->lv", xc, p_embed, quant)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(tc, 0)[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.where(
            tc >= 0, jax.nn.logsumexp(logits, axis=1) - picked, 0.0))

    total = jnp.sum(jax.lax.map(one, (xp.reshape(-1, chunk, x.shape[1]),
                                      tp.reshape(-1, chunk))))
    return total / jnp.maximum(jnp.sum(targets >= 0), 1)


# ---------------------------------------------------------------------------
# layer by layer
# ---------------------------------------------------------------------------

def _skey(spec):
    return tuple(sorted(spec.items()))


@functools.lru_cache(maxsize=None)
def _jitted_layer(kind: str, dense: bool, skey, quant):
    spec = dict(skey)

    def fwd(p, bias, x):
        return layer_forward(p, bias, x, spec, kind, dense, quant)

    def bwd(p, bias, x, dx):
        return jax.vjp(lambda p_, x_: fwd(p_, bias, x_), p, x)[1](dx)
    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _jitted_selected(kind: str, skey):
    spec = dict(skey)
    return jax.jit(lambda p, bias, x: selected(
        p, bias, ffn_input(p, x, spec, kind)[1], spec)[1])


@functools.lru_cache(maxsize=None)
def _jitted_head(skey, quant):
    spec = dict(skey)

    def both(e, norm, x, t):
        return jax.value_and_grad(
            lambda e_, n_, x_: head_loss(e_, n_, x_, t, spec, quant),
            argnums=(0, 1, 2))(e, norm, x)
    return jax.jit(both)


def _biases(stats, spec):
    """Each layer's selection bias (None for a dense layer), from the
    buffers handed in or, where there are none, the configuration's."""
    stats = stats if stats else default_stats(spec)
    return [None if dense else stats[f"layers_{i}"]["expert_bias"]
            for i, (_, dense) in enumerate(schedule(spec))]


def _row_forward(params, biases, ids, spec, quant=None, keep=None):
    x = params["embed"]["embedding"][ids]
    skey = _skey(spec)
    for i, (kind, dense) in enumerate(schedule(spec)):
        if keep is not None:
            keep.append(x)
        x = _jitted_layer(kind, dense, skey, quant)[0](
            params[f"layers_{i}"], biases[i], x)
    return x


def inference_forward(params, stats, ids, spec):
    """Logits (rows, L, vocabulary rows held) of the whole stack."""
    biases = _biases(stats, spec)
    outs = []
    for row in ids:
        x = rms_norm(_row_forward(params, biases, row, spec),
                     params["final_norm"]["scale"], spec["eps"])
        outs.append(jnp.einsum("ld,vd->lv", x, params["embed"]["embedding"],
                               precision=HIGHEST))
    return jnp.stack(outs)


def prologue(ids, step_index: int, aug: Dict[str, Any], seed: int):
    """The step is fed the ids as the host loader yields them."""
    return ids


def _row_loss_and_grads(params, biases, ids, targets, spec, quant):
    skey = _skey(spec)
    layers = schedule(spec)
    keep = []
    x = _row_forward(params, biases, ids, spec, quant, keep)
    loss, (d_e, d_norm, dx) = _jitted_head(skey, quant)(
        params["embed"]["embedding"], params["final_norm"], x, targets)
    grads = {"final_norm": d_norm}
    for i in reversed(range(len(layers))):
        grads[f"layers_{i}"], dx = _jitted_layer(*layers[i], skey, quant)[1](
            params[f"layers_{i}"], biases[i], keep[i], dx)
        keep[i] = None
    grads["embed"] = {"embedding": d_e.at[ids].add(dx)}
    return loss, grads


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
