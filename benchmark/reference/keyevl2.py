"""Plain reference of Keye-VL-2.0-30B-A3B's language model (grouped-query
attention over the keys a learned indexer selects, the indexer's KL loss, a
softmax router over routed experts held in part): forward, next-token loss
and gradients.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision (every
product names it, and the entry points run under
``jax.default_matmul_precision("highest")``), written from the layer
equations (``models/keyevl2.py``'s text has them) and the sizes the
configuration file states.  It imports nothing of the program under test
and calls no kernel (the control's rounding and the ``product`` it wraps
come from ``reference/phi4flash.py``, the norms, the rotation, the SwiGLU
and the head's chunked loss from ``reference/lfm2moe.py``).

* The selection is ``lax.top_k`` of the causal index scores of a block of
  queries against every key before its band's end (the non-causal ones at
  -inf): the ``topk`` largest, ties to the lower index; the attention and
  the indexer's KL of the block go over the one-hot mask of what it
  returned, causal keys only.  It goes by blocks of queries, each block made
  again in the backward pass, so no ``L x L`` array is ever whole.  The
  blocks are grouped in ``BANDS`` bands of the row, and a band's blocks read
  the keys up to the band's last query and no further: keys after a query
  are masked whatever their score, so leaving them out changes no number
  (``tests/test_keyevl2.py`` holds the bands to one band of every key) and
  saves 3/8 of the work at four bands.
* The expert layer is the plainest thing that is right: **every held expert
  is applied to every token** and its output multiplied by a weight that is
  0 where the token did not select it (no sort, no gather, no capacity),
  one expert after the other.  It routes over all the published experts and
  normalises over all eight selected.
* It runs layer by layer and row by row: one jitted forward and one jitted
  vector-Jacobian product a layer, gradients averaged over the rows.  The
  forward keeps each layer's input, the keys its queries picked and its
  attention's output; the backward takes them, pulls back the part after
  the attention by autodiff, then the attention block by block (each block
  made again and pulled back at once), then the part before it, so the
  selection is made once a step and each block twice.  A layer's result is
  its output and its indexer's mean KL over the row's positions; the loss a
  row minimises is its next-token loss plus the mean of those over the
  layers.  ``loss``, as returned, is the next-token loss alone (the
  program's metric).

Departures from the published description, all of layout and none of value:

* rotate-half pairing (channel ``i`` with ``i + dh / 2``), and the release's
  mRoPE taken as plain RoPE: for text its three position streams are equal;
* an expert's first product is one kernel ``[w1 | w3]``; the held experts
  are stacked: ``experts_w13`` (held, d, 2 x 768), ``experts_w2`` (held,
  768, d);
* the weight-decay mask, the clipping and Adam's bias correction live in
  ``optim_adamw.py``;
* a target of -1 marks the last position of a row (nothing follows it).

``quant`` is the control's hook, as in ``reference/lfm2moe.py``: ``"fp8"``
rounds the operands of every product the program makes in bfloat16 to
float8 and what flows between them to bfloat16; the router and the index
scores' weighting stay float32.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.lfm2moe import head_loss, rms_norm, rotate, swiglu
from benchmark.reference.phi4flash import _ACT, HIGHEST, layer_norm, product

Q_BLOCK = 64           # queries a block of attention takes
BANDS = 4              # bands of a row's blocks; a band reads keys to its end


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
    sa = config["sa_config"]
    spec = {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "dh": int(config["head_dim"]),
        "index_heads": int(sa["indexer_num_heads"]),
        "index_dim": int(sa["indexer_head_dim"]),
        "topk": int(sa["topk"]),
        "f": int(config["moe_intermediate_size"]),
        "eps": float(config["rms_norm_eps"]),
        "experts": int(config.get("num_experts_published",
                                  config["num_experts"])),
        "held": (int(config.get("held_first", 0)),
                 int(config["num_experts"])),
        "top_k": int(config["num_experts_per_tok"]),
        "theta": float(config["rope_theta"]),
        "layers": int(config["num_hidden_layers"]),
        "rows": int(config["vocab_size"]),
    }
    spec["num_classes"] = spec["rows"]
    assert config["norm_topk_prob"] and int(sa["indexer_num_kv_heads"]) == 1
    assert not config["tie_word_embeddings"] and not config["attention_bias"]
    assert config["hidden_act"] == "silu" and config["decoder_sparse_step"] \
        == 1 and not config["mlp_only_layers"]
    return spec


def forward_counts(config: Dict[str, Any]) -> Dict[str, float]:
    """Operations and bytes of ONE row of the configuration's length."""
    from benchmark.lib import flops_dsa
    return flops_dsa.counts_for(model_spec(config),
                                int(config["train"]["seq_len"]))


def _layer_shapes(s) -> Dict[str, Any]:
    d, h, hk, dh = s["d"], s["heads"], s["kv_heads"], s["dh"]
    nj, e = s["index_heads"], s["index_dim"]
    held = s["held"][1]
    return {"input_layernorm": {"scale": (d,)},
            "post_attention_layernorm": {"scale": (d,)},
            "q_proj": {"kernel": (d, h * dh)},
            "k_proj": {"kernel": (d, hk * dh)},
            "v_proj": {"kernel": (d, hk * dh)},
            "o_proj": {"kernel": (h * dh, d)},
            "q_norm": {"scale": (dh,)},
            "k_norm": {"scale": (dh,)},
            "index_q": {"kernel": (d, nj * e)},
            "index_k": {"kernel": (d, e)},
            "index_k_norm": {"scale": (e,), "bias": (e,)},
            "index_w": {"kernel": (d, nj)},
            "gate": (d, s["experts"]),
            "experts_w13": (held, d, 2 * s["f"]),
            "experts_w2": (held, s["f"], d)}


def param_shapes(spec):
    """(parameters, buffers): no buffers."""
    shapes = {"embed": {"embedding": (spec["rows"], spec["d"])},
              "lm_head": (spec["rows"], spec["d"]),
              "final_norm": {"scale": (spec["d"],)}}
    for i in range(spec["layers"]):
        shapes[f"layers_{i}"] = _layer_shapes(spec)
    return shapes, {}


def init_leaf(key, path: Tuple[str, ...], shape):
    """Seeded weights in sane ranges: fan-in kernels (an expert's fan-in is
    its second-to-last axis), norm scales around 1 and the indexer key
    norm's bias around 0, embedding and head std 0.02, each branch's last
    projection times 1 / sqrt(2 x 4 layers)."""
    name = path[-1]
    n = jax.random.normal(key, shape, jnp.float32)
    if name in ("embedding", "lm_head"):
        return 0.02 * n
    if name == "scale":
        return 1.0 + 0.1 * n
    if name == "bias":
        return 0.1 * n
    fan_in = shape[-2] if len(shape) > 1 else shape[0]
    last = name == "experts_w2" or (path[-1] == "kernel"
                                    and path[-2] == "o_proj")
    return n / math.sqrt(fan_in) * (1 / math.sqrt(8.0) if last else 1.0)


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def index_scores(qi, ki, w):
    """(Bq, L) ``sum_j w[t, j] relu(qi[t, j] . ki[s])``, float32."""
    x = jnp.einsum("tje,se->tjs", qi, ki, precision=HIGHEST)
    return jnp.einsum("tj,tjs->ts", w, jnp.maximum(x, 0.0),
                      precision=HIGHEST)


def _bands(l: int, bq: int):
    """[(first query, queries, keys)] of a row of ``l``: ``BANDS`` bands of
    whole blocks of ``bq`` queries, a band's keys the positions up to its
    last query."""
    n = -(-l // bq)
    per = -(-n // BANDS)
    return [(b * bq, min(l, (b + per) * bq) - b * bq, min(l, (b + per) * bq))
            for b in range(0, n, per)]


def _blocked(x, t0: int, n: int, bq: int):
    """Rows ``t0 .. t0 + n`` of ``x`` as (blocks, bq, ...), the last block
    padded with zeros."""
    x = x[t0:t0 + n]
    x = jnp.pad(x, ((0, -n % bq),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape((-1, bq) + x.shape[1:])


def _positions(t0: int, n: int, bq: int, kb: int):
    """(blocks, bq) positions of the band's queries; a padded query stands
    at the band's last key."""
    return _blocked(jnp.arange(kb), t0, n, bq) + jnp.pad(
        jnp.zeros(n, jnp.int32), (0, -n % bq),
        constant_values=kb - 1).reshape(-1, bq)


def attention_inputs(p, z, s, quant):
    """``(q (L, H, D), k, v (L, Hk, D), qi (L, J, E), ki (L, E), w (L, J))``
    of one row's normed input ``z``: the indexer's from the held ``z``."""
    l = z.shape[0]
    h, hk, dh = s["heads"], s["kv_heads"], s["dh"]
    nj, e = s["index_heads"], s["index_dim"]
    act = _ACT[quant]
    q = product("ld,de->le", z, p["q_proj"]["kernel"], quant).reshape(
        l, h, dh)
    k = product("ld,de->le", z, p["k_proj"]["kernel"], quant).reshape(
        l, hk, dh)
    v = act(product("ld,de->le", z, p["v_proj"]["kernel"], quant).reshape(
        l, hk, dh))
    q = act(rotate(rms_norm(q, p["q_norm"]["scale"], s["eps"]), s["theta"]))
    k = act(rotate(rms_norm(k, p["k_norm"]["scale"], s["eps"]), s["theta"]))
    # the indexer reads the held input
    zi = jax.lax.stop_gradient(z)
    qi = act(rotate(product("ld,de->le", zi, p["index_q"]["kernel"],
                            quant).reshape(l, nj, e), s["theta"]))
    ki = layer_norm(product("ld,de->le", zi, p["index_k"]["kernel"], quant),
                    p["index_k_norm"], s["eps"])
    ki = act(rotate(ki[:, None, :], s["theta"])[:, 0])
    w = jnp.einsum("ld,dj->lj", zi, p["index_w"]["kernel"],
                   precision=HIGHEST) / math.sqrt(nj * e)
    return q, k, v, qi, ki, w


def selection(qi, ki, w, tb, topk):
    """(Bq, min(topk, keys)) int32: the keys ``lax.top_k`` picks for a block
    of queries at positions ``tb``: the causal keys of largest index score,
    ties to the lower index; past the causal keys it picks masked ones,
    which the mask of :func:`block` leaves out."""
    scores = index_scores(qi, ki, w)
    causal = jnp.arange(ki.shape[0])[None, :] <= tb[:, None]
    return jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                         min(topk, ki.shape[0]))[1]


def block(q, k, v, qi, ki, w, tb, picked, s, quant):
    """``(o (Bq, H, D), kl (Bq,))`` of a block of queries at positions
    ``tb`` against the keys before the band's end (``k``, ``v`` (kb, Hk,
    D), ``ki`` (kb, E)), over the keys ``picked`` (:func:`selection`)."""
    bq, kb = q.shape[0], k.shape[0]
    hk, dh = s["kv_heads"], s["dh"]
    act = _ACT[quant]
    scores = index_scores(qi, ki, w)
    causal = jnp.arange(kb)[None, :] <= tb[:, None]
    sel = jnp.zeros((bq, kb), bool).at[
        jnp.arange(bq)[:, None], picked].set(True) & causal
    # query head h reads key head h // (H / Hk)
    qg = q.reshape(bq, hk, -1, dh)
    sc = product("qgrd,kgd->grqk", qg, k, quant) / math.sqrt(dh)
    a = jax.nn.softmax(jnp.where(sel, sc, -jnp.inf), axis=-1)
    o = product("grqk,kgd->qgrd", act(a), v, quant).reshape(q.shape)
    pbar = jax.lax.stop_gradient(jnp.mean(a, axis=(0, 1)))
    masked = jnp.where(sel, scores, -jnp.inf)
    logq = jnp.where(sel, masked - jax.nn.logsumexp(
        masked, axis=-1, keepdims=True), 0.0)
    plogp = jnp.where(pbar > 0, pbar * jnp.log(jnp.where(
        pbar > 0, pbar, 1.0)), 0.0)
    kl = jnp.sum(jnp.where(sel, plogp - pbar * logq, 0.0), axis=-1)
    return o, kl


def _picks(qi, ki, w, s):
    """The keys each band's queries pick, (blocks, Bq, k) int32 a band."""
    l = qi.shape[0]
    bq = min(Q_BLOCK, l)
    return [jax.lax.map(
        lambda a, kb=kb: selection(a[0], ki[:kb], a[1], a[2], s["topk"]),
        (_blocked(qi, t0, n, bq), _blocked(w, t0, n, bq),
         _positions(t0, n, bq, kb)))
        for t0, n, kb in _bands(l, bq)]


def sparse_attention(ins, picked, s, quant):
    """``(o (L, H, D), kl (L,))``: the attention of a row over the selected
    keys (before the output projection) and the indexer's KL of each
    position, from the row's :func:`attention_inputs` and the keys each band
    picked (:func:`_picks`)."""
    q, k, v, qi, ki, w = ins
    l = q.shape[0]
    bq = min(Q_BLOCK, l)
    outs, kls = [], []
    for (t0, n, kb), pick in zip(_bands(l, bq), picked):
        o, kl = jax.lax.map(jax.checkpoint(
            lambda a, kb=kb: block(a[0], k[:kb], v[:kb], a[1], ki[:kb],
                                   a[2], a[3], a[4], s, quant)),
            (_blocked(q, t0, n, bq), _blocked(qi, t0, n, bq),
             _blocked(w, t0, n, bq), _positions(t0, n, bq, kb), pick))
        outs.append(o.reshape((-1,) + o.shape[2:])[:n])
        kls.append(kl.reshape(-1)[:n])
    return jnp.concatenate(outs), jnp.concatenate(kls)


def sparse_attention_vjp(ins, picked, do, dkl, s, quant):
    """The cotangents of :func:`sparse_attention`'s inputs for ``do`` (L, H,
    D) and ``dkl`` (L,): block by block, each block made again and pulled
    back at once, the keys' cotangents summed over the band's blocks."""
    q, k, v, qi, ki, w = ins
    l = q.shape[0]
    bq = min(Q_BLOCK, l)
    dq, dqi, dw = (jnp.zeros_like(a) for a in (q, qi, w))
    dk, dv, dki = (jnp.zeros_like(a) for a in (k, v, ki))
    for (t0, n, kb), pick in zip(_bands(l, bq), picked):
        def pull(acc, a, kb=kb):
            qb, qib, wb, tb, pb, dob, dklb = a
            _, back = jax.vjp(lambda qb_, k_, v_, qib_, ki_, wb_: block(
                qb_, k_, v_, qib_, ki_, wb_, tb, pb, s, quant),
                qb, k[:kb], v[:kb], qib, ki[:kb], wb)
            gq, gk, gv, gqi, gki, gw = back((dob, dklb))
            return (acc[0] + gk, acc[1] + gv, acc[2] + gki), (gq, gqi, gw)
        (gk, gv, gki), (gq, gqi, gw) = jax.lax.scan(
            pull, (jnp.zeros_like(k[:kb]), jnp.zeros_like(v[:kb]),
                   jnp.zeros_like(ki[:kb])),
            (_blocked(q, t0, n, bq), _blocked(qi, t0, n, bq),
             _blocked(w, t0, n, bq), _positions(t0, n, bq, kb), pick,
             _blocked(do, t0, n, bq), _blocked(dkl, t0, n, bq)))
        dq = dq.at[t0:t0 + n].set(gq.reshape((-1,) + q.shape[1:])[:n])
        dqi = dqi.at[t0:t0 + n].set(gqi.reshape((-1,) + qi.shape[1:])[:n])
        dw = dw.at[t0:t0 + n].set(gw.reshape((-1,) + w.shape[1:])[:n])
        dk = dk.at[:kb].add(gk)
        dv = dv.at[:kb].add(gv)
        dki = dki.at[:kb].add(gki)
    return dq, dk, dv, dqi, dki, dw


def routing_weights(p, x, s):
    """(L, experts) float32: a selected expert's weight, 0 elsewhere: the
    softmax over all experts, kept for the top k and renormalised over
    them."""
    r = jax.nn.softmax(jnp.einsum("ld,de->le", x, p["gate"],
                                  precision=HIGHEST), axis=-1)
    sel = jax.lax.top_k(r, s["top_k"])[1]
    chosen = jnp.sum(jax.nn.one_hot(sel, s["experts"], dtype=jnp.float32),
                     axis=1)
    picked = r * chosen
    return picked / jnp.sum(picked, axis=1, keepdims=True)


def experts(p, x, s, quant):
    """Every held expert on every token times the token's weight for it,
    summed one expert after the other."""
    first, held = s["held"]
    w = routing_weights(p, x, s)
    xq = _ACT[quant](x)

    @jax.checkpoint
    def one(y, e):
        return y + jax.lax.dynamic_index_in_dim(w, first + e, axis=1) * \
            swiglu(xq, p["experts_w13"][e], p["experts_w2"][e], s["f"],
                   quant), None
    return jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))[0]


def layer_inputs(p, x, s, quant=None):
    """:func:`attention_inputs` of the layer's input ``x``."""
    z = _ACT[quant](rms_norm(x, p["input_layernorm"]["scale"], s["eps"]))
    return attention_inputs(p, z, s, quant)


def layer_output(p, x, o, s, quant=None):
    """The layer's output from its input ``x`` and its attention ``o``."""
    o = _ACT[quant](o.reshape(o.shape[0], -1))
    x = x + product("le,ed->ld", o, p["o_proj"]["kernel"], quant)
    y = rms_norm(x, p["post_attention_layernorm"]["scale"], s["eps"])
    return x + experts(p, y, s, quant)


def layer_forward(p, x, s, quant=None):
    """One layer on one row: (x (L, d) out, the indexer's mean KL)."""
    ins = layer_inputs(p, x, s, quant)
    o, kl = sparse_attention(ins, _picks(*ins[3:], s), s, quant)
    return layer_output(p, x, o, s, quant), jnp.mean(kl)


def _picked(p, x, s, quant=None):
    """The keys each band of the layer's queries picks for its input
    ``x``."""
    return _picks(*layer_inputs(p, x, s, quant)[3:], s)


# ---------------------------------------------------------------------------
# layer by layer
# ---------------------------------------------------------------------------

def _skey(spec):
    return tuple(sorted(spec.items()))


@functools.lru_cache(maxsize=None)
def _jitted_layer(skey, quant):
    """(forward, backward) of a layer: the forward gives the layer's output
    and mean KL, and keeps the keys picked and the attention's output; the
    backward takes them, so the selection is made once a step and each
    block twice (forward, and again where it is pulled back)."""
    spec = dict(skey)

    def fwd(p, x):
        ins = layer_inputs(p, x, spec, quant)
        picked = _picks(*ins[3:], spec)
        o, kl = sparse_attention(ins, picked, spec, quant)
        return (layer_output(p, x, o, spec, quant), jnp.mean(kl)), \
            (picked, o)

    def bwd(p, x, kept, dx, dkl):
        picked, o = kept
        _, after = jax.vjp(
            lambda p_, x_, o_: layer_output(p_, x_, o_, spec, quant), p, x, o)
        dp_after, dx_after, do = after(dx)
        ins, before = jax.vjp(
            lambda p_, x_: layer_inputs(p_, x_, spec, quant), p, x)
        dkl_rows = jnp.full((x.shape[0],), dkl / x.shape[0])
        dp_before, dx_before = before(sparse_attention_vjp(
            ins, picked, do, dkl_rows, spec, quant))
        return jax.tree.map(jnp.add, dp_after, dp_before), \
            dx_after + dx_before
    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _jitted_head(skey, quant):
    spec = dict(skey)

    def both(w, norm, x, t):
        return jax.value_and_grad(
            lambda w_, n_, x_: head_loss(w_, n_, x_, t, spec, quant),
            argnums=(0, 1, 2))(w, norm, x)
    return jax.jit(both)


def _row_forward(params, ids, spec, quant=None, keep=None):
    """(final hidden states before the norm, the layers' mean KL); ``keep``
    gets each layer's input and what its backward takes of its forward."""
    x = params["embed"]["embedding"][ids]
    skey = _skey(spec)
    kls = []
    for i in range(spec["layers"]):
        x_in = x
        (x, kl), kept = _jitted_layer(skey, quant)[0](
            params[f"layers_{i}"], x)
        if keep is not None:
            keep.append((x_in, kept))
        kls.append(kl)
    return x, sum(kls) / len(kls)


@_highest
def inference_forward(params, stats, ids, spec):
    """Logits (rows, L, vocabulary rows held) of the whole stack."""
    outs = []
    for row in ids:
        x = rms_norm(_row_forward(params, row, spec)[0],
                     params["final_norm"]["scale"], spec["eps"])
        outs.append(jnp.einsum("ld,vd->lv", x, params["lm_head"],
                               precision=HIGHEST))
    return jnp.stack(outs)


def prologue(ids, step_index: int, aug: Dict[str, Any], seed: int):
    """The step is fed the ids as the host loader yields them."""
    return ids


def _row_loss_and_grads(params, ids, targets, spec, quant, kl_weight):
    """(next-token loss, the layers' mean KL, gradients of the next-token
    loss plus ``kl_weight`` times the mean KL) of one row."""
    skey = _skey(spec)
    keep = []
    x, kl = _row_forward(params, ids, spec, quant, keep)
    loss, (d_head, d_norm, dx) = _jitted_head(skey, quant)(
        params["lm_head"], params["final_norm"], x, targets)
    grads = {"final_norm": d_norm, "lm_head": d_head}
    dkl = jnp.float32(kl_weight / spec["layers"])
    for i in reversed(range(spec["layers"])):
        grads[f"layers_{i}"], dx = _jitted_layer(skey, quant)[1](
            params[f"layers_{i}"], *keep[i], dx, dkl)
        keep[i] = None
    grads["embed"] = {"embedding": jnp.zeros_like(
        params["embed"]["embedding"]).at[ids].add(dx)}
    return loss, kl, grads


@_highest
def loss_and_grads(params, stats, x, y, spec, quant=None):
    """Loss and gradients of a batch of rows: ids ``x`` and targets ``y``
    (rows, L).  The loss is the next-token loss, a mean over every
    position with a target (each row weighed by its share of the targets);
    the gradients are of that plus the indexer's KL, a mean over the rows'
    positions and the layers."""
    rows = x.shape[0]
    counts = [int(jnp.sum(y[r] >= 0)) for r in range(rows)]
    total = max(sum(counts), 1)
    loss, grads = 0.0, None
    for r in range(rows):
        w = counts[r] / total
        l_r, _, g_r = _row_loss_and_grads(params, x[r], y[r], spec, quant,
                                          1.0 / (rows * w) if w else 0.0)
        loss = loss + w * l_r
        g_r = jax.tree.map(lambda g: w * g, g_r)
        grads = g_r if grads is None else jax.tree.map(jnp.add, grads, g_r)
    return loss, grads, stats, None

