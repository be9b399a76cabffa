"""Plain reference of the SambaY stack (Phi-4-mini-flash-reasoning): forward,
next-token loss and gradients.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the layer equations (arXiv 2507.06607 for the stack, 2410.05258 for
differential attention, Mamba-1 for the recurrence) and the sizes the
configuration file states.  It imports nothing of the program under test and
calls no kernel.  It runs layer by layer: one jitted forward and one jitted
vector-Jacobian product per layer kind, the inputs of each layer kept, so
the whole model is never one float32 program and the 16,384-token row fits.
Inside a layer, attention goes by blocks of queries against all keys (the
mask does the rest: a window layer computes every score and drops most),
the recurrence by chunks of the sequence with a step-by-step ``lax.scan``
inside, and the logits by chunks of positions; each of those is made again
in the backward pass instead of being kept.

Departures from the layer equations, all of layout and none of value:

* query heads are group-major, as the configuration's ``assumed`` says: head
  ``(g*2 + i)*r + j`` is softmax map ``i`` of query pair ``j`` of KV pair
  ``g``; key head ``2g + i``; value pair ``g`` is heads ``2g, 2g+1`` side by
  side (128 wide);
* the weight-decay mask, the clipping and Adam's bias correction live in
  ``optim_adamw.py``;
* a target of -1 marks the last position of a row (nothing follows it).

Parameter names follow the tree the program's checkpoints use
(``layers_<l>/in_proj/kernel`` ...): the benchmark makes the weights once
from the seed and hands the same tree to both sides.

``quant`` is the control's hook: ``None`` computes as above; ``"fp8"`` rounds
the operands of every matrix product (projections, scores, values, head) to
float8 e4m3 scaled per tensor, their cotangents to e5m2, and what flows
between them to bfloat16: one precision below the bfloat16 the
configuration states.  The recurrence stays float32 on both sides.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
Q_BLOCK = 256          # queries a block of attention takes
SCAN_CHUNK = 256       # steps a chunk of the recurrence takes
HEAD_CHUNK = 1024      # positions a chunk of the logits takes


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------

def model_spec(config: Dict[str, Any]) -> Dict[str, Any]:
    """Sizes from the configuration file: the published keys at its top
    level, the sizes the published config lacks under ``assumed``, the
    periods kept of each part under ``layout``."""
    a, lay = config["assumed"], config["layout"]
    d = int(config["hidden_size"])
    spec = {
        "d": d, "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "dh": d // int(config["num_attention_heads"]),
        "ff": int(config["intermediate_size"]),
        "window": int(config["sliding_window"]),
        "eps": float(config["layer_norm_eps"]),
        "n": int(a["d_state"]), "conv": int(a["d_conv"]),
        "inner": int(a["expand"]) * d, "rank": int(a["dt_rank"]),
        "self_periods": int(lay["self_periods"]),
        "cross_periods": int(lay["cross_periods"]),
        "rows": int(config["vocab_size"]),
    }
    spec["num_classes"] = spec["rows"]
    assert 2 * (spec["self_periods"] + spec["cross_periods"] + 1) == \
        int(config["num_hidden_layers"])
    return spec


def schedule(spec) -> Tuple[str, ...]:
    return (MAMBA, WINDOW) * spec["self_periods"] + (MAMBA, FULL) + \
        (GMU, CROSS) * spec["cross_periods"]


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _layer_shapes(kind: str, s) -> Dict[str, Any]:
    d, ff, inner = s["d"], s["ff"], s["inner"]
    out = {"ln1": {"scale": (d,), "bias": (d,)},
           "ln2": {"scale": (d,), "bias": (d,)},
           "gate_up": {"kernel": (d, 2 * ff)}, "down": {"kernel": (ff, d)}}
    if kind == MAMBA:
        out.update({
            "in_proj": {"kernel": (d, 2 * inner)},
            "conv_kernel": (s["conv"], inner), "conv_bias": (inner,),
            "x_proj": {"kernel": (inner, s["rank"] + 2 * s["n"])},
            "dt_proj_kernel": (s["rank"], inner), "dt_proj_bias": (inner,),
            "A_log": (inner, s["n"]), "D": (inner,),
            "out_proj": {"kernel": (inner, d)}})
    elif kind == GMU:
        out.update({"in_proj": {"kernel": (d, inner)},
                    "out_proj": {"kernel": (inner, d)}})
    else:
        hq = s["heads"] * s["dh"]
        if kind == CROSS:
            out["q"] = {"kernel": (d, hq), "bias": (hq,)}
        else:
            wide = (s["heads"] + 2 * s["kv_heads"]) * s["dh"]
            out["qkv"] = {"kernel": (d, wide), "bias": (wide,)}
        out["out_proj"] = {"kernel": (hq, d), "bias": (d,)}
        out["subln_scale"] = (2 * s["dh"],)
        for n in ("q1", "k1", "q2", "k2"):
            out[f"lambda_{n}"] = (s["dh"],)
    return out


def param_shapes(spec):
    shapes = {"embed": {"embedding": (spec["rows"], spec["d"])},
              "final_ln": {"scale": (spec["d"],), "bias": (spec["d"],)}}
    for i, kind in enumerate(schedule(spec)):
        shapes[f"layers_{i}"] = _layer_shapes(kind, spec)
    return shapes, {}


def residual_gains(spec) -> Dict[Tuple[str, ...], float]:
    """The last projection of every branch, scaled by 1/sqrt(branches): the
    residual stream's variance then stays of order one through the stack."""
    g = 1.0 / math.sqrt(2 * len(schedule(spec)))
    out = {}
    for i in range(len(schedule(spec))):
        out[(f"layers_{i}", "down", "kernel")] = g
        out[(f"layers_{i}", "out_proj", "kernel")] = g
    return out


def init_leaf(key, path: Tuple[str, ...], shape):
    """Seeded weights in sane ranges: fan-in kernels; norm scales around 1;
    ``A_log`` = log of 1..N (so A in [-N, -1]) with 5% jitter; the step
    bias such that softplus(bias) is log-uniform in [1e-3, 1e-1]; lambda
    vectors of std 0.1 (lambda then starts near lambda_init); D around 1."""
    name = path[-1]
    n = jax.random.normal(key, shape, jnp.float32)
    if name == "embedding":
        return 0.02 * n
    if name == "kernel" or name == "dt_proj_kernel":
        return n / math.sqrt(shape[0])
    if name == "conv_kernel":
        return n / math.sqrt(shape[0])
    if name in ("scale", "subln_scale", "D"):
        return 1.0 + 0.1 * n
    if name == "A_log":
        return jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)) \
            + 0.05 * n
    if name == "dt_proj_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))      # softplus's inverse
    if name.startswith("lambda_"):
        return 0.1 * n
    return 0.02 * n                                # biases


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 after scaling the tensor's largest magnitude to
    the format's largest (448); straight-through gradient."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, 448.0 / amax, 1.0)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)


def _bf16(x):
    q = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _grad_e5m2(x):
    """Identity whose cotangent is rounded to float8 e5m2 (scaled per
    tensor): the gradient operand of a float8 training recipe."""
    return x


def _grad_e5m2_fwd(x):
    return x, None


def _grad_e5m2_bwd(_, g):
    amax = jnp.max(jnp.abs(g))
    s = jnp.where(amax > 0, 57344.0 / amax, 1.0)
    return ((g * s).astype(jnp.float8_e5m2).astype(jnp.float32) / s,)


_grad_e5m2.defvjp(_grad_e5m2_fwd, _grad_e5m2_bwd)

_ID = lambda x: x                                          # noqa: E731
_OPERAND = {None: _ID, "fp8": _fp8, "bf16": _bf16}
_PRODUCT = {None: _ID, "bf16": _ID, "fp8": lambda y: _bf16(_grad_e5m2(y))}
_ACT = {None: _ID, "bf16": _ID, "fp8": _bf16}


def product(expr: str, a, b, quant=None):
    q = _OPERAND[quant]
    return _PRODUCT[quant](jnp.einsum(expr, q(a), q(b), precision=HIGHEST))


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def mlp(p, x, s, quant):
    y = product("ld,df->lf", layer_norm(x, p["ln2"], s["eps"]),
                p["gate_up"]["kernel"], quant)
    g, u = y[:, :s["ff"]], y[:, s["ff"]:]
    return product("lf,fd->ld", _ACT[quant](silu(g) * u),
                   p["down"]["kernel"], quant)


def recurrence(u, delta, a, bm, cm, skip):
    """s_t = exp(delta_t A) s_{t-1} + delta_t B_t u_t; y_t = C_t . s_t +
    D u_t.  u, delta (L, D); a (D, N); bm, cm (L, N).  A chunk of steps at a
    time, each chunk made again in the backward pass."""
    l, d = u.shape
    chunk = min(SCAN_CHUNK, l)
    pad = -l % chunk
    if pad:     # delta = 0: the state stands still
        u, delta, bm, cm = (jnp.pad(x, ((0, pad), (0, 0)))
                            for x in (u, delta, bm, cm))

    @jax.checkpoint
    def one_chunk(state, xs):
        def step(st, x):
            u_t, d_t, b_t, c_t = x
            st = jnp.exp(d_t[:, None] * a) * st \
                + (d_t * u_t)[:, None] * b_t[None, :]
            return st, jnp.sum(st * c_t[None, :], axis=1)
        return jax.lax.scan(step, state, xs)

    xs = tuple(x.reshape(-1, chunk, x.shape[1]) for x in (u, delta, bm, cm))
    _, y = jax.lax.scan(one_chunk, jnp.zeros((d, a.shape[1]), jnp.float32),
                        xs)
    return y.reshape(-1, d)[:l] + skip * u[:l]


def mamba(p, x, s, quant):
    """Returns (the mixer's output, the scan's output before the gate)."""
    inner, n, r = s["inner"], s["n"], s["rank"]
    uz = product("ld,de->le", x, p["in_proj"]["kernel"], quant)
    u, z = uz[:, :inner], uz[:, inner:]
    l = u.shape[0]
    padded = jnp.pad(u, ((s["conv"] - 1, 0), (0, 0)))
    u = sum(padded[k:k + l] * p["conv_kernel"][k]
            for k in range(s["conv"])) + p["conv_bias"]
    u = _ACT[quant](silu(u))
    dbc = product("le,ef->lf", u, p["x_proj"]["kernel"], quant)
    delta = jax.nn.softplus(
        product("lr,re->le", dbc[:, :r], p["dt_proj_kernel"], quant)
        + p["dt_proj_bias"])
    y = recurrence(u, delta, -jnp.exp(p["A_log"]), dbc[:, r:r + n],
                   dbc[:, r + n:], p["D"])
    out = product("le,ed->ld", _ACT[quant](y * silu(z)),
                  p["out_proj"]["kernel"], quant)
    return out, y


def diff_attention(p, q, k, v, s, layer: int, window, quant):
    """q (L, H, dh) group-major; k (L, Hkv, dh); v (L, Hkv/2, 2 dh).
    Returns the (L, H dh) input of W_o."""
    l, h, dh = q.shape
    pk = k.shape[1] // 2
    r = h // k.shape[1]
    kk = jnp.repeat(k, r, axis=1)                 # key head of each q head
    vv = jnp.repeat(v, 2 * r, axis=1)             # value pair of each q head
    pos = jnp.arange(l)

    @jax.checkpoint
    def block(args):
        qb, tb = args                              # (Bq, H, dh), (Bq,)
        sc = product("qhd,khd->hqk", qb, kk, quant) / math.sqrt(dh)
        ok = pos[None, :] <= tb[:, None]
        if window is not None:
            ok = ok & (tb[:, None] - pos[None, :] < window)
        a = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        return product("hqk,khe->qhe", _ACT[quant](a), vv, quant)

    bq = min(Q_BLOCK, l)
    pad = -l % bq
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    tp = jnp.pad(pos, (0, pad), constant_values=l - 1)
    o = jax.lax.map(block, (qp.reshape(-1, bq, h, dh), tp.reshape(-1, bq)))
    o = o.reshape(-1, h, 2 * dh)[:l].reshape(l, pk, 2, r, 2 * dh)
    li = lambda_init(layer)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + li
    o = o[:, :, 0] - lam * o[:, :, 1]              # (L, pk, r, 2 dh)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-5) \
        * p["subln_scale"] * (1.0 - li)
    return _ACT[quant](o.reshape(l, h * dh))


def attention(p, x, mem, s, kind, layer, quant):
    """Returns (the mixer's output, (k, v) as this layer made them)."""
    l = x.shape[0]
    h, hk, dh = s["heads"], s["kv_heads"], s["dh"]
    if kind == CROSS:
        q = product("ld,de->le", x, p["q"]["kernel"], quant) + p["q"]["bias"]
        k, v = mem
    else:
        qkv = product("ld,de->le", x, p["qkv"]["kernel"], quant) \
            + p["qkv"]["bias"]
        q = qkv[:, :h * dh]
        k = qkv[:, h * dh:(h + hk) * dh].reshape(l, hk, dh)
        v = qkv[:, (h + hk) * dh:].reshape(l, hk // 2, 2 * dh)
    o = diff_attention(p, _ACT[quant](q).reshape(l, h, dh), _ACT[quant](k),
                       _ACT[quant](v), s, layer,
                       s["window"] if kind == WINDOW else None, quant)
    out = product("le,ed->ld", o, p["out_proj"]["kernel"], quant) \
        + p["out_proj"]["bias"]
    return out, (k, v)


def layer_forward(p, x, mem, s, kind, layer, quant=None):
    """One layer on one row: x (L, d).  ``mem`` is the producer's scan
    output (GMU), the full layer's (k, v) (cross) or ().  Returns (x, what
    the layer hands on: its scan output, its (k, v), or ())."""
    y = _ACT[quant](layer_norm(x, p["ln1"], s["eps"]))
    out = ()
    if kind == MAMBA:
        y, out = mamba(p, y, s, quant)
    elif kind == GMU:
        g = silu(product("ld,de->le", y, p["in_proj"]["kernel"], quant))
        y = product("le,ed->ld", _ACT[quant](g * mem),
                    p["out_proj"]["kernel"], quant)
    else:
        y, kv = attention(p, y, mem, s, kind, layer, quant)
        out = kv if kind == FULL else ()
    x = x + y
    return x + mlp(p, x, s, quant), out


def head_loss(p_embed, p_ln, x, targets, s, quant=None):
    """Mean next-token cross-entropy over the positions with a target."""
    x = _ACT[quant](layer_norm(x, p_ln, s["eps"]))
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
def _jitted_layer(kind: str, layer: int, skey, quant):
    spec = dict(skey)

    def fwd(p, x, mem):
        return layer_forward(p, x, mem, spec, kind, layer, quant)

    def bwd(p, x, mem, dx, dout):
        _, vjp = jax.vjp(fwd, p, x, mem)
        return vjp((dx, dout))
    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _jitted_head(skey, quant):
    spec = dict(skey)

    def both(e, ln, x, t):
        return jax.value_and_grad(
            lambda e_, ln_, x_: head_loss(e_, ln_, x_, t, spec, quant),
            argnums=(0, 1, 2))(e, ln, x)
    return jax.jit(both)


def _mem_of(kind, memory, kv):
    return memory if kind == GMU else kv if kind == CROSS else ()


def _row_forward(params, ids, spec, quant=None, keep=None):
    x = params["embed"]["embedding"][ids]
    skey = _skey(spec)
    memory = kv = ()
    for i, kind in enumerate(schedule(spec)):
        mem = _mem_of(kind, memory, kv)
        if keep is not None:
            keep.append((x, mem))
        x, out = _jitted_layer(kind, i, skey, quant)[0](
            params[f"layers_{i}"], x, mem)
        if kind == MAMBA:
            memory = out
        elif kind == FULL:
            kv = out
    return x


def inference_forward(params, stats, ids, spec):
    """Logits (rows, L, vocabulary rows held) of the whole stack."""
    del stats
    outs = []
    for row in ids:
        x = layer_norm(_row_forward(params, row, spec), params["final_ln"],
                       spec["eps"])
        outs.append(jnp.einsum("ld,vd->lv", x, params["embed"]["embedding"],
                               precision=HIGHEST))
    return jnp.stack(outs)


def prologue(ids, step_index: int, aug: Dict[str, Any], seed: int):
    """The step is fed the ids as the host loader yields them."""
    return ids


def _row_loss_and_grads(params, ids, targets, spec, quant):
    skey = _skey(spec)
    kinds = schedule(spec)
    keep = []
    x = _row_forward(params, ids, spec, quant, keep)
    (loss, (d_e, d_ln, dx)) = _jitted_head(skey, quant)(
        params["embed"]["embedding"], params["final_ln"], x, targets)
    grads = {"final_ln": d_ln}
    last_mamba = max(i for i, k in enumerate(kinds) if k == MAMBA)
    d_memory = d_kv = None
    add = lambda a, b: b if a is None else jax.tree.map(jnp.add, a, b)  # noqa
    for i in reversed(range(len(kinds))):
        kind = kinds[i]
        xin, mem = keep[i]
        bwd = _jitted_layer(kind, i, skey, quant)[1]
        p = params[f"layers_{i}"]
        if kind == MAMBA:
            dout = d_memory if i == last_mamba and d_memory is not None \
                else jnp.zeros((xin.shape[0], spec["inner"]), jnp.float32)
        elif kind == FULL:
            dout = d_kv if d_kv is not None else jax.tree.map(
                jnp.zeros_like, _kv_like(xin.shape[0], spec))
        else:
            dout = ()
        grads[f"layers_{i}"], dx, dmem = bwd(p, xin, mem, dx, dout)
        if kind == GMU:
            d_memory = add(d_memory, dmem)
        elif kind == CROSS:
            d_kv = add(d_kv, dmem)
        keep[i] = None
    grads["embed"] = {"embedding": d_e.at[ids].add(dx)}
    return loss, grads


def _kv_like(l, spec):
    return (jnp.zeros((l, spec["kv_heads"], spec["dh"]), jnp.float32),
            jnp.zeros((l, spec["kv_heads"] // 2, 2 * spec["dh"]),
                      jnp.float32))


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
