"""Plain reference of the granite-4.0-h stack (Mamba-2 layers with NoPE
grouped attention among them, muP multipliers): forward, next-token loss and
gradients.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the layer equations (ISSUE 30 spells them out; Mamba-2 is arXiv
2405.21060) and the sizes the configuration file states.  It imports nothing
of the program under test and calls no kernel (the control's rounding and
the ``product`` it wraps come from ``reference/phi4flash.py``).  The
recurrence goes **step by step**, one position at a time over the ``(H, P,
N)`` state, inside
``jax.checkpoint``ed segments (so the backward keeps the segments' first
states and one segment's states): it uses none of the dual form's algebra
-- no chunk, no decay matrix, no matrix product -- because that is what it
checks.  Attention goes by blocks of queries against all keys, the logits by
chunks of positions; each is made again in the backward pass instead of
being kept.  It runs layer by layer: one jitted forward and one jitted
vector-Jacobian product per layer kind, the inputs of each layer kept, so
the whole model is never one float32 program and the 16,384-token row fits.

Departures from the published description, all of layout and none of value:

* the attention projections are one kernel, columns ``[q | k | v]`` (32 x
  64, 8 x 64, 8 x 64); the release keeps three;
* ``in_proj``'s columns are ``[z | xBC | dt]`` and ``xBC`` is ``[x | B |
  C]``, as the release has them; the convolution's kernel is stored (taps,
  channels), tap ``k`` multiplying position ``t - 3 + k``;
* the MLP's first product is one kernel ``[gate | up]``, as the release's
  ``shared_mlp.input_linear``;
* the weight-decay mask, the clipping and Adam's bias correction live in
  ``optim_adamw.py``;
* a target of -1 marks the last position of a row (nothing follows it).

What the published config does not say follows the released implementation's
defaults and is the configuration's ``assumed``: no clamp on the step, the
gate before the gated RMSNorm, one norm group over all the channels.

Parameter names follow the tree the program's checkpoints use
(``layers_<l>/in_proj/kernel`` ...): the benchmark makes the weights once
from the seed and hands the same tree to both sides.

``quant`` is the control's hook: ``None`` computes as above; ``"fp8"`` rounds
the operands of every matrix product (projections, scores, values, head,
and the recurrence's ``x``, ``B``, ``C``, which the dual form multiplies) to
float8 e4m3 scaled per tensor, their cotangents to e5m2, and what flows
between them to bfloat16: one precision below the bfloat16 the
configuration states.  The state, the steps and the decay stay float32.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# the control's rounding (``quant``) and the product it wraps are the SambaY
# reference's: one definition of "one precision lower" for both families
from benchmark.reference.phi4flash import (_ACT, _OPERAND, _PRODUCT,
                                           HIGHEST, product, silu)

MAMBA, ATTENTION = "mamba", "attention"
Q_BLOCK = 256          # queries a block of attention takes
SEGMENT = 256          # steps a checkpointed segment of the recurrence takes
HEAD_CHUNK = 1024      # positions a chunk of the logits takes


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------

def model_spec(config: Dict[str, Any]) -> Dict[str, Any]:
    """Sizes from the configuration file's published keys; the layers are
    the first ``num_hidden_layers`` of ``layer_types``."""
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    spec = {
        "d": d, "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]), "dh": d // heads,
        "ff": int(config["shared_intermediate_size"]),
        "eps": float(config["rms_norm_eps"]),
        "ssm_heads": int(config["mamba_n_heads"]),
        "p": int(config["mamba_d_head"]), "n": int(config["mamba_d_state"]),
        "conv": int(config["mamba_d_conv"]),
        "chunk": int(config["mamba_chunk_size"]),
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "residual_multiplier": float(config["residual_multiplier"]),
        "attention_multiplier": float(config["attention_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        "kinds": tuple(config["layer_types"][
            :int(config["num_hidden_layers"])]),
        "rows": int(config["vocab_size"]),
    }
    spec["inner"] = spec["ssm_heads"] * spec["p"]
    spec["num_classes"] = spec["rows"]
    assert spec["inner"] == int(config["mamba_expand"]) * d
    assert int(config["mamba_n_groups"]) == 1, "B and C are shared by heads"
    assert config["position_embedding_type"] == "nope"
    assert set(spec["kinds"]) <= {MAMBA, ATTENTION}
    return spec


def schedule(spec) -> Tuple[str, ...]:
    return spec["kinds"]


def forward_counts(config: Dict[str, Any]) -> Dict[str, float]:
    """Operations and bytes of ONE row of the configuration's length."""
    from benchmark.lib import flops_ssd
    spec = model_spec(config)
    return flops_ssd.counts_for(spec, schedule(spec),
                                int(config["train"]["seq_len"]))


def _layer_shapes(kind: str, s) -> Dict[str, Any]:
    d, ff, inner, n = s["d"], s["ff"], s["inner"], s["n"]
    out = {"norm1": {"scale": (d,)}, "norm2": {"scale": (d,)},
           "gate_up": {"kernel": (d, 2 * ff)}, "down": {"kernel": (ff, d)}}
    if kind == MAMBA:
        conv = inner + 2 * n
        out.update({
            "in_proj": {"kernel": (d, inner + conv + s["ssm_heads"])},
            "conv_kernel": (s["conv"], conv), "conv_bias": (conv,),
            "dt_bias": (s["ssm_heads"],), "A_log": (s["ssm_heads"],),
            "D": (s["ssm_heads"],), "norm_scale": (inner,),
            "out_proj": {"kernel": (inner, d)}})
    else:
        hq = s["heads"] * s["dh"]
        out["qkv"] = {"kernel": (d, hq + 2 * s["kv_heads"] * s["dh"])}
        out["out_proj"] = {"kernel": (hq, d)}
    return out


def param_shapes(spec):
    shapes = {"embed": {"embedding": (spec["rows"], spec["d"])},
              "final_norm": {"scale": (spec["d"],)}}
    for i, kind in enumerate(schedule(spec)):
        shapes[f"layers_{i}"] = _layer_shapes(kind, spec)
    return shapes, {}


def init_leaf(key, path: Tuple[str, ...], shape):
    """Seeded weights in sane ranges: fan-in kernels (the residual
    multiplier is the model's own branch scale, so none is added); norm
    scales and D around 1; ``A_log`` = log U(1, 16) (A in [-16, -1]); the
    step bias such that softplus(bias) is log-uniform in [1e-3, 1e-1]."""
    name = path[-1]
    n = jax.random.normal(key, shape, jnp.float32)
    if name == "embedding":
        return 0.02 * n
    if name in ("kernel", "conv_kernel"):
        return n / math.sqrt(shape[0])
    if name in ("scale", "norm_scale", "D"):
        return 1.0 + 0.1 * n
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))      # softplus's inverse
    return 0.02 * n                                # the convolution's bias


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * scale


def mlp(p, x, s, quant):
    y = product("ld,df->lf", _ACT[quant](
        rms_norm(x, p["norm2"]["scale"], s["eps"])),
        p["gate_up"]["kernel"], quant)
    g, u = y[:, :s["ff"]], y[:, s["ff"]:]
    return product("lf,fd->ld", _ACT[quant](silu(g) * u),
                   p["down"]["kernel"], quant)


def recurrence(x, dt, a, bm, cm, skip):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D x_t.
    x (L, H, P); dt (L, H); a, skip (H,); bm, cm (L, N).  One position at a
    time; a segment of steps is made again in the backward pass."""
    l, h, p = x.shape
    seg = min(SEGMENT, l)
    pad = -l % seg
    if pad:     # dt = 0: the state stands still
        x, dt, bm, cm = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                         for v in (x, dt, bm, cm))

    @jax.checkpoint
    def segment(state, xs):
        def step(st, inp):
            x_t, dt_t, b_t, c_t = inp
            st = jnp.exp(dt_t * a)[:, None, None] * st \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
            return st, jnp.sum(st * c_t[None, None, :], axis=-1)
        return jax.lax.scan(step, state, xs)

    xs = tuple(v.reshape((-1, seg) + v.shape[1:]) for v in (x, dt, bm, cm))
    _, y = jax.lax.scan(segment,
                        jnp.zeros((h, p, bm.shape[1]), jnp.float32), xs)
    return y.reshape(-1, h, p)[:l] + skip[:, None] * x[:l]


def mamba(p, x, s, quant):
    inner, n, h = s["inner"], s["n"], s["ssm_heads"]
    zxd = product("ld,de->le", x, p["in_proj"]["kernel"], quant)
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:2 * inner + 2 * n], \
        zxd[:, 2 * inner + 2 * n:]
    l = x.shape[0]
    padded = jnp.pad(xbc, ((s["conv"] - 1, 0), (0, 0)))
    xbc = sum(padded[k:k + l] * p["conv_kernel"][k]
              for k in range(s["conv"])) + p["conv_bias"]
    xbc = _ACT[quant](silu(xbc))
    q = _OPERAND[quant]
    u, bm, cm = q(xbc[:, :inner]), q(xbc[:, inner:inner + n]), \
        q(xbc[:, inner + n:])
    dt = jax.nn.softplus(dt + p["dt_bias"])          # no clamp on the step
    y = _PRODUCT[quant](recurrence(
        u.reshape(l, h, s["p"]), dt, -jnp.exp(p["A_log"]), bm, cm, p["D"]))
    # the gate first, then one RMSNorm over all the channels
    g = rms_norm(y.reshape(l, inner) * silu(_ACT[quant](z)),
                 p["norm_scale"], s["eps"])
    return product("le,ed->ld", _ACT[quant](g), p["out_proj"]["kernel"],
                   quant)


def attention(p, x, s, quant):
    """Causal softmax attention, four query heads to one KV head, no
    positional encoding, the scores times ``attention_multiplier``."""
    l = x.shape[0]
    h, hk, dh = s["heads"], s["kv_heads"], s["dh"]
    qkv = _ACT[quant](product("ld,de->le", x, p["qkv"]["kernel"], quant))
    q = qkv[:, :h * dh].reshape(l, h, dh)
    k = jnp.repeat(qkv[:, h * dh:(h + hk) * dh].reshape(l, hk, dh),
                   h // hk, axis=1)
    v = jnp.repeat(qkv[:, (h + hk) * dh:].reshape(l, hk, dh), h // hk, axis=1)
    pos = jnp.arange(l)

    @jax.checkpoint
    def block(args):
        qb, tb = args                              # (Bq, H, dh), (Bq,)
        sc = product("qhd,khd->hqk", qb, k, quant) * s["attention_multiplier"]
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


def layer_forward(p, x, s, kind, quant=None):
    """One layer on one row: x (L, d)."""
    y = _ACT[quant](rms_norm(x, p["norm1"]["scale"], s["eps"]))
    y = mamba(p, y, s, quant) if kind == MAMBA else attention(p, y, s, quant)
    x = x + s["residual_multiplier"] * y
    return x + s["residual_multiplier"] * mlp(p, x, s, quant)


def head_loss(p_embed, p_norm, x, targets, s, quant=None):
    """Mean next-token cross-entropy over the positions with a target, the
    logits over ``logits_scaling``."""
    x = _ACT[quant](rms_norm(x, p_norm["scale"], s["eps"]))
    l = x.shape[0]
    chunk = min(HEAD_CHUNK, l)
    pad = -l % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    tp = jnp.pad(targets, (0, pad), constant_values=-1)

    @jax.checkpoint
    def one(args):
        xc, tc = args
        logits = product("ld,vd->lv", xc, p_embed, quant) \
            / s["logits_scaling"]
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
def _jitted_layer(kind: str, skey, quant):
    spec = dict(skey)

    def fwd(p, x):
        return layer_forward(p, x, spec, kind, quant)

    def bwd(p, x, dx):
        return jax.vjp(fwd, p, x)[1](dx)
    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _jitted_head(skey, quant):
    spec = dict(skey)

    def both(e, norm, x, t):
        return jax.value_and_grad(
            lambda e_, n_, x_: head_loss(e_, n_, x_, t, spec, quant),
            argnums=(0, 1, 2))(e, norm, x)
    return jax.jit(both)


def _row_forward(params, ids, spec, quant=None, keep=None):
    x = spec["embedding_multiplier"] * params["embed"]["embedding"][ids]
    skey = _skey(spec)
    for i, kind in enumerate(schedule(spec)):
        if keep is not None:
            keep.append(x)
        x = _jitted_layer(kind, skey, quant)[0](params[f"layers_{i}"], x)
    return x


def inference_forward(params, stats, ids, spec):
    """Logits (rows, L, vocabulary rows held) of the whole stack."""
    del stats
    outs = []
    for row in ids:
        x = rms_norm(_row_forward(params, row, spec),
                     params["final_norm"]["scale"], spec["eps"])
        outs.append(jnp.einsum("ld,vd->lv", x, params["embed"]["embedding"],
                               precision=HIGHEST) / spec["logits_scaling"])
    return jnp.stack(outs)


def prologue(ids, step_index: int, aug: Dict[str, Any], seed: int):
    """The step is fed the ids as the host loader yields them."""
    return ids


def _row_loss_and_grads(params, ids, targets, spec, quant):
    skey = _skey(spec)
    kinds = schedule(spec)
    keep = []
    x = _row_forward(params, ids, spec, quant, keep)
    loss, (d_e, d_norm, dx) = _jitted_head(skey, quant)(
        params["embed"]["embedding"], params["final_norm"], x, targets)
    grads = {"final_norm": d_norm}
    for i in reversed(range(len(kinds))):
        grads[f"layers_{i}"], dx = _jitted_layer(kinds[i], skey, quant)[1](
            params[f"layers_{i}"], keep[i], dx)
        keep[i] = None
    grads["embed"] = {"embedding": d_e.at[ids].add(
        spec["embedding_multiplier"] * dx)}
    return loss, grads


def loss_and_grads(params, stats, x, y, spec, quant=None):
    """Loss and gradients of a batch of rows: ids ``x`` and targets ``y``
    (rows, L), the loss a mean over every position with a target."""
    rows = x.shape[0]
    counts = [int(jnp.sum(y[r] >= 0)) for r in range(rows)]
    total = max(sum(counts), 1)
    loss, grads = 0.0, None
    for r in range(rows):
        l_r, g_r = _row_loss_and_grads(params, x[r], y[r], spec, quant)
        w = counts[r] / total
        loss = loss + w * l_r
        g_r = jax.tree.map(lambda g: w * g, g_r)
        grads = g_r if grads is None else jax.tree.map(jnp.add, grads, g_r)
    return loss, grads, stats, None
