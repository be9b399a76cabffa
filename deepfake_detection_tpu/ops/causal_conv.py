"""Causal depthwise convolution over the sequence, bias and activation: one op.

The Mamba mixers (``models/granite4h.py``, ``models/phi4flash.py``) convolve
every channel of ``x`` (batch, L, C) with its own ``d_conv`` taps, looking
back only::

    pre[l] = b + sum_k w[k] * x[l - (d_conv - 1) + k]        (x[< 0] = 0)
    y      = silu(pre)

Written as a sum of shifted slices, XLA answers ``jax.grad`` with the
``d_conv`` shifted products written to memory, read back for ``dx``, and a
row reduction of its own for ``dw`` and ``db``: about twenty passes over the
tensor where seven are needed (PERF.md section 6, PR 31).  Here the op has
its own backward, which makes the pre-activation again from the residuals
``x``, ``w``, ``b`` and keeps ``dpre`` and the shifted products out of
memory.  Products, sums, bias and activation are float32 whatever ``x``'s
dtype; the result is rounded once.

Two forms of the same two passes (``impl``):

* ``"xla"``: array operations, the path of the CPU tests and of any shape
  the kernels do not take.
* ``"pallas"``: two TPU kernels over (batch, tile of rows).  A tile holds
  every lane of its rows, so it is one run of memory, and is read once; the
  rows before it are a second small block of the same operand (zeros
  before row 0).  Inside, a loop takes two lane tiles at a time, so that
  what is live stays in registers, and the taps are sublane shifts of that
  slab.  The backward walks the tiles from the last to the first, carrying
  the first rows of ``dpre`` to the tile before, and sums ``dw`` and ``db``
  over the rows in a block that stays in VMEM, eight sublanes a tap, folded
  outside.  ``impl=None`` takes the kernels on a TPU backend where ``C`` is
  a multiple of 128 and ``L`` of the row tile, and ``"xla"`` elsewhere.

The row tile and the slab come from the chip (a v5e, both cells' shapes, PR
31; ``tools/bench_causal_conv.py``): blocks of (512 rows, 256 lanes) read
0.85 ms a forward launch where a plain copy through the same pipeline reads
0.49-0.57 ms; whole rows of 128 with the slab loop read 0.58 ms (the
backward 1.12 ms for 1.64), and the cell's step 870.4 ms for 880.2; rows of
256 read 874.2 ms.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the activation and its derivative in float32, by name
from .depthwise_pallas import _act_f32 as _act, _act_grad_f32 as _act_grad
from .flash_attention import _out_struct, _scratch, resolve_interpret

__all__ = ["causal_conv1d", "causal_conv_census", "causal_conv_impl"]

_LANES = 128
_SUB = 8                        # float32 sublanes: what a carried halo holds
_HALO = 16                      # rows fetched before a tile: one bf16 tile
_ROWS = 128                     # rows a grid cell takes, every lane of them


# ---------------------------------------------------------------------------
# array form
# ---------------------------------------------------------------------------

def _pre_xla(x, w, b):
    """(pre, the rows padded in front) in float32."""
    k, l = w.shape[0], x.shape[1]
    pad = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    return sum(pad[:, i:i + l] * w[i] for i in range(k)) + b, pad


def _forward_xla(x, w, b, act):
    return _act(act)(_pre_xla(x, w, b)[0]).astype(x.dtype)


def _backward_xla(x, w, b, dy, act):
    k, l = w.shape[0], x.shape[1]
    pre, pad = _pre_xla(x, w, b)
    dpre = dy.astype(jnp.float32) * _act_grad(act)(pre)
    after = jnp.pad(dpre, ((0, 0), (0, k - 1), (0, 0)))
    dx = sum(after[:, k - 1 - i:k - 1 - i + l] * w[i] for i in range(k))
    dw = jnp.stack([jnp.sum(dpre * pad[:, i:i + l], (0, 1))
                    for i in range(k)])
    return dx, dw, jnp.sum(dpre, (0, 1))


# ---------------------------------------------------------------------------
# the same two passes as TPU kernels
# ---------------------------------------------------------------------------

def _slab(c: int) -> int:
    """Lanes a kernel works on at a time: two lane tiles where they divide
    ``c``."""
    return 2 * _LANES if c % (2 * _LANES) == 0 else _LANES


def _over_slabs(c: int, body) -> None:
    """``body(lanes)`` for every slab of the block's ``c`` lanes in turn:
    what is live at a time is a slab's, and stays in registers."""
    slab = _slab(c)

    def one(j, carry):
        body(pl.ds(pl.multiple_of(j * slab, slab), slab))
        return carry
    lax.fori_loop(0, c // slab, one, 0)


def _pre_kernel(x_ref, halo_ref, w_ref, b_ref, lanes, first):
    """(pre, the tile's views shifted back by 0 .. d_conv - 1 rows) of one
    slab, float32 (rows, lanes).  ``halo_ref`` holds the ``_HALO`` rows
    before the tile; ``first``: there are none, the tile starts the
    sequence."""
    f32 = jnp.float32
    k = w_ref.shape[0]
    x = x_ref[0, :, lanes].astype(f32)
    t = x.shape[0]
    halo = jnp.where(first, 0.0,
                     halo_ref[0, :, lanes].astype(f32)[_HALO - _SUB:])
    ext = jnp.concatenate([halo, x], axis=0)
    views = [x] + [ext[_SUB - s:_SUB - s + t] for s in range(1, k)]
    pre = b_ref[:, lanes] + sum(views[k - 1 - i] * w_ref[i:i + 1, lanes]
                                for i in range(k))
    return pre, views


def _fwd_kernel(x_ref, halo_ref, w_ref, b_ref, y_ref, *, act):
    first = pl.program_id(1) == 0

    def slab(lanes):
        pre, _ = _pre_kernel(x_ref, halo_ref, w_ref, b_ref, lanes, first)
        y_ref[0, :, lanes] = _act(act)(pre).astype(y_ref.dtype)
    _over_slabs(x_ref.shape[2], slab)


def _fold(v):
    """(rows, lanes) -> (8, lanes): the rows summed sublane by sublane."""
    return jnp.sum(v.reshape(v.shape[0] // _SUB, _SUB, v.shape[1]), axis=0)


def _bwd_kernel(x_ref, halo_ref, dy_ref, w_ref, b_ref, dx_ref, sums_ref,
                next_ref, *, act):
    """One (batch, tile counted from the end) cell.  ``next_ref`` (8, C)
    carries the first rows of ``dpre`` of the tile after this one;
    ``sums_ref`` (1, d_conv + 1, 8, C) stays resident over the tiles and
    gathers the taps' and the bias's sums."""
    t, nt = pl.program_id(1), pl.num_programs(1)
    k = w_ref.shape[0]

    @pl.when(t == 0)
    def _():
        next_ref[...] = jnp.zeros_like(next_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def slab(lanes):
        pre, views = _pre_kernel(x_ref, halo_ref, w_ref, b_ref, lanes,
                                 t == nt - 1)
        dpre = dy_ref[0, :, lanes].astype(jnp.float32) * _act_grad(act)(pre)
        rows = dpre.shape[0]
        ext = jnp.concatenate([dpre, next_ref[:, lanes]], axis=0)
        dx = sum(ext[k - 1 - i:k - 1 - i + rows] * w_ref[i:i + 1, lanes]
                 for i in range(k))
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        next_ref[:, lanes] = dpre[:_SUB]
        for i in range(k):
            sums_ref[0, i, :, lanes] += _fold(dpre * views[k - 1 - i])
        sums_ref[0, k, :, lanes] += _fold(dpre)
    _over_slabs(x_ref.shape[2], slab)


def _specs(c, k, tile_of):
    """The specs of x, halo, w, b: whole rows, so a block is one run of
    memory.  ``tile_of`` maps the grid's inner index to the tile of rows."""
    per = _ROWS // _HALO
    wide = pl.BlockSpec((1, _ROWS, c), lambda i, t: (i, tile_of(t), 0))
    halo = pl.BlockSpec(
        (1, _HALO, c),
        lambda i, t: (i, jnp.maximum(tile_of(t) * per - 1, 0), 0))
    taps = pl.BlockSpec((k, c), lambda i, t: (0, 0))
    bias = pl.BlockSpec((1, c), lambda i, t: (0, 0))
    return wide, halo, taps, bias


def _forward_pallas(x, w, b, act, interpret):
    n, l, c = x.shape
    wide, halo, taps, bias = _specs(c, w.shape[0], lambda t: t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, act=act),
        grid=(n, l // _ROWS),
        in_specs=[wide, halo, taps, bias],
        out_specs=wide,
        out_shape=_out_struct(x.shape, x.dtype, x),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 2,
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(x, x, w, b.reshape(1, c))


def _backward_pallas(x, w, b, dy, act, interpret):
    n, l, c = x.shape
    k, nt = w.shape[0], l // _ROWS
    wide, halo, taps, bias = _specs(c, k, lambda t: nt - 1 - t)
    dx, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, act=act),
        grid=(n, nt),
        in_specs=[wide, halo, wide, taps, bias],
        out_specs=[wide, pl.BlockSpec((1, k + 1, _SUB, c),
                                      lambda i, t: (i, 0, 0, 0))],
        out_shape=[_out_struct(x.shape, x.dtype, x),
                   _out_struct((n, k + 1, _SUB, c), jnp.float32, x)],
        scratch_shapes=[_scratch((_SUB, c))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(x, x, dy, w, b.reshape(1, c))
    sums = jnp.sum(sums, (0, 2))
    return dx, sums[:k], sums[k]


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv(x, w, b, act, pallas, interpret):
    return _forward_pallas(x, w, b, act, interpret) if pallas \
        else _forward_xla(x, w, b, act)


def _conv_fwd(x, w, b, act, pallas, interpret):
    return _conv(x, w, b, act, pallas, interpret), (x, w, b)


def _conv_bwd(act, pallas, interpret, res, dy):
    x, w, b = res
    dx, dw, db = _backward_pallas(x, w, b, dy, act, interpret) if pallas \
        else _backward_xla(x, w, b, dy, act)
    return dx.astype(x.dtype), dw, db


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_impl(l: int, c: int, backend: Optional[str] = None) -> str:
    """The form ``causal_conv1d(impl=None)`` takes for ``L`` rows of ``C``
    channels: the kernels on a TPU backend where whole tiles hold the shape,
    the array form elsewhere.  ``backend``: what the program is compiled
    for, where that is not this process's default backend (a described
    chip)."""
    tiles = c % _LANES == 0 and l % _ROWS == 0
    return "pallas" if tiles and \
        (backend or jax.default_backend()) == "tpu" else "xla"


def causal_conv_census(layers: int, l: int, c: int):
    """``layers`` convolutions over ``L`` rows of ``C`` channels by the form
    ``impl=None`` gives them, (kernels, array form): what a model's
    ``causal_conv_layers`` reports."""
    return (layers, 0) if causal_conv_impl(l, c) == "pallas" else (0, layers)


def causal_conv1d(x, w, b, activation: Optional[str] = "silu",
                  impl: Optional[str] = None,
                  interpret: Optional[bool] = None):
    """``x`` (batch, L, C); ``w`` (d_conv, C), tap ``d_conv - 1`` on the
    row itself; ``b`` (C,) or None for a convolution without bias.  Returns
    ``activation(conv + b)`` in ``x``'s dtype; ``activation`` is ``"silu"``
    or None.  ``impl``: ``"xla"``,
    ``"pallas"`` (``interpret`` as for the other kernels: compiled on a TPU,
    interpreted elsewhere; ``C`` a multiple of 128 and ``L`` of the row tile
    of 128) or None, which :func:`causal_conv_impl` decides."""
    assert activation in ("silu", None), activation
    l, c = x.shape[1:]
    if b is None:
        b = jnp.zeros((c,), jnp.float32)
    assert w.shape[1:] == (c,) and b.shape == (c,), (x.shape, w.shape, b.shape)
    if impl is None:
        impl = causal_conv_impl(l, c)
    assert impl in ("xla", "pallas"), impl
    pallas = impl == "pallas"
    if pallas:
        assert c % _LANES == 0 and l % _ROWS == 0 and w.shape[0] <= _SUB + 1,\
            (x.shape, w.shape)
        interpret = resolve_interpret(interpret, "causal_conv1d")
    f32 = jnp.float32
    return _conv(x, w.astype(f32), b.astype(f32), activation or "none",
                 pallas, bool(interpret))
