"""A routed expert layer (mixture of experts) held in part: router, dispatch
without dropped tokens, grouped matrix products over the experts held,
combine.

The router scores **all** ``E`` experts of the published layer with a
sigmoid, *selects* the top ``k`` of a token by score plus a per-expert bias
and *weighs* them by the score without it, normalised over the ``k``
selected (:func:`route`; ``models/lfm2moe.py`` has the equations); or, by
the second rule (:func:`route_softmax`, ``models/keyevl2.py``), with a
softmax over all ``E``, the top ``k`` of it renormalised over the ``k``.  A chip of an
expert-parallel deployment holds ``held = (first, count)`` of the experts:
it routes over all ``E`` and computes its own experts' part of the sum,

    y[t] = sum_{e in S(t), first <= e < first + count} w[t, e] * FFN_e(z[t])

so that the parts of the chips add up to the published layer (the
normalisation is over all ``k`` selected, held here or not).

:func:`expert_ffn` is exact for any routing: no capacity factor and no
dropped assignment.  The ``T * k`` (token, selected expert) assignments are
sorted by the held expert they fell on (those that fell on none last); the
first ``total`` sorted rows are the work.  Shapes are static, so the rows
are taken at one of two **capacities**, the smaller that holds ``total``,
by a ``lax.switch``: twice the share of ``T * k`` that falls on the held
experts when the routing is uniform (``2 * count / E``), and all of ``T *
k``; one capacity where the first is all of it (half of the experts held,
or more).  The gather, the elementwise passes and the scatter walk that
many rows, and the grouped products walk the rows of the groups only.
:func:`expert_ffn` says beside its result whether the pass took every row,
and :func:`routing_counts` carries that to the step's metrics.  The op has
its own backward, which makes the switch again and differentiates the taken
branch inside it, so that nothing a branch keeps is ever written for the
branches not taken (``jax.grad`` of a switch hands every branch's residuals
out of every branch, zeros for those not taken: the full-capacity buffers
on every step); its residuals are its arguments, and the hidden activations
are made again in the backward.

Two forms of the grouped product, one algorithm around them (``impl``):

* ``"xla"``: ``jax.lax.ragged_dot``, differentiated by jax: the CPU tests'
  path and any shape the kernel does not take;
* ``"pallas"``: the megablox grouped-matmul kernels that ship with jax
  (``jax.experimental.pallas.ops.tpu.megablox``), forward and both backward
  products (``gmm`` with the weights transposed for the rows' gradient,
  ``tgmm`` for the weights'), tiles past the last group skipped.  What they
  leave unwritten (rows past ``total``) is masked before it is used.

``impl=None`` takes what :func:`moe_impl` says: the kernels on a TPU
backend where the tiles divide the shapes, ``"xla"`` elsewhere (PERF.md
section 6, PR 32, has what each read in the cell's own step).

Router product, sigmoid, selection and normalisation are float32; the
expert products run in ``z``'s dtype with float32 accumulation.
"""

from __future__ import annotations

import functools
import importlib
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import resolve_interpret

__all__ = ["Routing", "expert_ffn", "moe_census", "moe_impl", "route",
           "route_softmax", "routing_counts"]

# rows a grid cell of the grouped-product kernels takes, and its tiles of
# the contracted and the output dimension (a v5e, the cell's shapes: PERF.md
# section 6, PR 32)
_TILING = (512, 1024, 1024)
# the weights' gradient leaves its kernel in float32 and its accumulator is
# as large again: a tile of 1024 x 1024 passes the 16 MB of scoped VMEM
_TGMM_TILING = (512, 512, 1024)
# the first capacity over the rows that fall on the held experts when the
# routing is uniform (T * k * count / E); every row where that is not enough
_HEADROOM = 2.0


class Routing(NamedTuple):
    """``sel`` (T, k) int32: the experts a token selected, of all ``E``;
    ``weight`` (T, k) float32: their weights, normalised over the ``k``."""
    sel: jax.Array
    weight: jax.Array


def route(logits, bias, k: int, scale: float = 1.0,
          norm_eps: float = 1e-6) -> Routing:
    """``logits`` (T, E) of the gate, ``bias`` (E,): the selection bias.
    ``s = sigmoid(logits)``; the top ``k`` of ``s + bias`` are selected and
    weigh ``s / (sum of the selected s + norm_eps) * scale``: the bias
    selects, it does not weigh.  All float32."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, sel = lax.top_k(s + bias.astype(jnp.float32), k)
    picked = jnp.take_along_axis(s, sel, axis=-1)
    weight = picked / (jnp.sum(picked, -1, keepdims=True) + norm_eps) * scale
    return Routing(sel.astype(jnp.int32), weight)


def route_softmax(logits, k: int) -> Routing:
    """The second rule: ``r = softmax(logits)`` over all ``E`` experts, the
    top ``k`` of ``r`` selected, weighing ``r / (sum of the selected r)``;
    no bias, no scale.  All float32."""
    r = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    picked, sel = lax.top_k(r, k)
    return Routing(sel.astype(jnp.int32),
                   picked / jnp.sum(picked, -1, keepdims=True))


def _held_key(sel, held: Tuple[int, int]):
    """(T, k) the held expert an assignment fell on, ``count`` for none."""
    first, count = held
    local = sel - first
    return jnp.where((local >= 0) & (local < count), local, count)


def routing_counts(sel, held: Tuple[int, int], full=0):
    """(5,) int32 of one pass through one layer: tokens routed, assignments
    that fell on held experts, the fullest held expert's assignments, that
    times the experts held (what the layer would hold were every held
    expert as full: over the assignments, the load's peak to its mean), and
    ``full``: 1 where :func:`expert_ffn` said the pass took every row."""
    sizes = jnp.bincount(_held_key(sel, held).reshape(-1),
                         length=held[1] + 1)[:held[1]]
    peak = jnp.max(sizes)
    return jnp.stack([jnp.int32(sel.shape[0]), jnp.sum(sizes), peak,
                      peak * held[1], full]).astype(jnp.int32)


# ---------------------------------------------------------------------------
# the grouped product, two forms
# ---------------------------------------------------------------------------

def _tiles(m: int, k: int, n: int, tiling=None) -> Tuple[int, int, int]:
    """The kernels' tiling for an (m, k) x (groups, k, n) product: the
    chip's, clipped to the shape (a tile of the contracted or the output
    dimension may overhang; the rows' tile has to divide ``m``)."""
    tm, tk, tn = tiling or _TILING
    while m % tm:
        tm //= 2
    return max(tm, 8), _fit(k, tk), _fit(n, tn)


def _fit(x: int, limit: int) -> int:
    """The largest multiple of 128 up to ``limit`` that divides ``x``;
    ``x`` itself where it is smaller, ``limit`` where none does."""
    if x <= limit:
        return x
    return next((t for t in range(limit - limit % 128, 0, -128)
                 if x % t == 0), limit)


def _megablox():
    """The kernels' module (the package re-exports a function under its
    name, so ``from ... import gmm`` gives that)."""
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(x, w, sizes, interpret):
    """Rows ``x`` (m, k), grouped by ``sizes``, times ``w`` (groups, k, n)
    float32 parameters, in ``x``'s dtype: the megablox kernel."""
    G = _megablox()
    return G.gmm(x, w.astype(x.dtype), sizes, x.dtype,
                 _tiles(x.shape[0], w.shape[1], w.shape[2]),
                 interpret=interpret)


def _gmm_fwd(x, w, sizes, interpret):
    return _gmm(x, w, sizes, interpret), (x, w, sizes)


def _gmm_bwd(interpret, res, dy):
    G = _megablox()
    x, w, sizes = res
    m, (_, k, n) = x.shape[0], w.shape
    dx = G.gmm(dy, w.astype(x.dtype), sizes, x.dtype, _tiles(m, n, k),
               transpose_rhs=True, interpret=interpret)
    # the weights' gradient leaves the kernel in float32: the sum over the
    # rows is not rounded to the compute dtype on its way to the optimizer
    dw = G.tgmm(x.swapaxes(0, 1), dy, sizes, jnp.float32,
                _tiles(m, k, n, _TGMM_TILING),
                num_actual_groups=w.shape[0], interpret=interpret)
    return dx, dw, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _grouped(x, w, sizes, pallas: bool, interpret: bool):
    if pallas:
        return _gmm(x, w, sizes, interpret)
    return lax.ragged_dot(x, w.astype(x.dtype), sizes,
                          preferred_element_type=jnp.float32).astype(x.dtype)


# ---------------------------------------------------------------------------
# dispatch, experts, combine at one capacity
# ---------------------------------------------------------------------------

def _ffn_at(cap: int, pallas: bool, interpret: bool, z, ws, w13, w2, tok,
            sizes, total):
    """The first ``cap`` sorted assignments through their experts.  ``tok``
    (A,) the token of a sorted assignment, ``ws`` (A,) its weight, ``sizes``
    the held experts' group sizes, ``total`` their sum (<= cap)."""
    f = w2.shape[1]
    valid = (jnp.arange(cap) < total)[:, None]
    with jax.named_scope("moe_dispatch"):
        rows = tok[:cap]
        x = jnp.where(valid, z[rows], 0)
    with jax.named_scope("moe_experts"):
        # the kernels write the groups' rows only: mask before use
        h = jnp.where(valid, _grouped(x, w13, sizes, pallas, interpret), 0)
        a = (jax.nn.silu(h[:, :f].astype(jnp.float32))
             * h[:, f:].astype(jnp.float32)).astype(z.dtype)
        y = jnp.where(valid, _grouped(a, w2, sizes, pallas, interpret), 0)
    with jax.named_scope("moe_combine"):
        y = y.astype(jnp.float32) * ws[:cap, None]
        out = jnp.zeros(z.shape, jnp.float32).at[rows].add(y)
        return out.astype(z.dtype)


def _tier(total, caps: Sequence[int]):
    """The first capacity that holds ``total`` rows (one capacity: 0)."""
    return sum(((total > c).astype(jnp.int32) for c in caps[:-1]),
               jnp.int32(0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _tiered(caps, pallas, interpret, z, ws, w13, w2, tok, sizes, total):
    branches = [functools.partial(_ffn_at, c, pallas, interpret)
                for c in caps]
    return lax.switch(_tier(total, caps), branches, z, ws, w13, w2, tok,
                      sizes, total)


def _tiered_fwd(caps, pallas, interpret, *args):
    return _tiered(caps, pallas, interpret, *args), args


def _tiered_bwd(caps, pallas, interpret, args, dout):
    z, ws, w13, w2, tok, sizes, total = args

    def grads_at(cap, z, ws, w13, w2, dout):
        fn = functools.partial(_ffn_at, cap, pallas, interpret)
        _, vjp = jax.vjp(lambda z_, ws_, a_, b_: fn(
            z_, ws_, a_, b_, tok, sizes, total), z, ws, w13, w2)
        return vjp(dout)

    grads = lax.switch(_tier(total, caps),
                       [functools.partial(grads_at, c) for c in caps],
                       z, ws, w13, w2, dout)
    return tuple(grads) + (None, None, None)


_tiered.defvjp(_tiered_fwd, _tiered_bwd)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

def moe_impl(tokens: int, k: int, d: int, f: int,
             backend: Optional[str] = None) -> str:
    """The form ``expert_ffn(impl=None)`` takes for ``tokens`` tokens of
    width ``d`` through experts of width ``f``, ``k`` selected a token: the
    kernels on a TPU backend where their lane tiles divide the widths and
    the assignments fill whole row tiles, ``"xla"`` elsewhere.  ``backend``:
    what the program is compiled for, where that is not this process's
    default backend (a described chip)."""
    fits = d % 128 == 0 and f % 128 == 0 and (tokens * k) % _TILING[0] == 0
    return "pallas" if fits and \
        (backend or jax.default_backend()) == "tpu" else "xla"


def moe_census(layers: int, tokens: int, k: int, d: int, f: int):
    """``layers`` expert layers by the form ``impl=None`` gives them,
    (kernels, array form): what a model's ``moe_layers`` reports."""
    return (layers, 0) if moe_impl(tokens, k, d, f) == "pallas" \
        else (0, layers)


def _capacities(n: int, count: int, experts: int, unit: int):
    """Rows the ``n`` sorted assignments are taken at (``n`` a multiple of
    ``unit``): ``_HEADROOM`` times the ``count / experts`` of them that
    fall on the held experts when the routing is uniform, rounded up to
    ``unit``, then all ``n``; ``(n,)`` where the first is all of them."""
    first = -(-int(n * _HEADROOM * count / experts) // unit) * unit
    return (first, n) if 0 < first < n else (n,)


def expert_ffn(z, routing: Routing, w13, w2, held: Tuple[int, int],
               experts: int, impl: Optional[str] = None,
               interpret: Optional[bool] = None):
    """The held experts' part of the routed layer.

    ``z`` (T, d); ``routing`` from :func:`route`; ``w13`` (count, d, 2 f),
    columns ``[w1 | w3]``, and ``w2`` (count, f, d): the SwiGLU experts
    ``held = (first, count)`` of the layer's ``experts``, float32
    parameters.  Returns (T, d) in ``z``'s dtype: ``sum_e w[t, e] *
    w2_e(silu(w1_e z) * w3_e z)`` over the selected experts that are held;
    and an int32 scalar, 1 where the pass took every one of its ``T * k``
    rows (always, where there is one capacity)."""
    t, d = z.shape
    k = routing.sel.shape[1]
    count, f = held[1], w2.shape[1]
    assert w13.shape == (count, d, 2 * f) and w2.shape == (count, f, d), \
        (w13.shape, w2.shape, held)
    if impl is None:
        impl = moe_impl(t, k, d, f)
    assert impl in ("xla", "pallas"), impl
    pallas = impl == "pallas"
    unit = _tiles(t * k, d, f)[0] if pallas else 1
    if pallas:
        assert (t * k) % unit == 0, (t, k, unit)
        interpret = resolve_interpret(interpret, "expert_ffn")
    with jax.named_scope("moe_dispatch"):
        key = _held_key(routing.sel, held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
        total = jnp.sum(sizes)
        tok = (order // k).astype(jnp.int32)
        ws = routing.weight.reshape(-1)[order]
    caps = _capacities(t * k, count, experts, unit)
    y = _tiered(caps, pallas, bool(interpret), z, ws, w13, w2, tok, sizes,
                total)
    return y, (_tier(total, caps) == len(caps) - 1).astype(jnp.int32)
