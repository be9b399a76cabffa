"""State-space dual scan (the Mamba-2 recurrence), chunked over the sequence.

For every head ``h`` (``P`` channels, one scalar decay), with the ``N``-wide
``B_t`` and ``C_t`` shared by all heads (one group)::

    S_t = exp(dt_t[h] * a[h]) * S_{t-1} + dt_t[h] * x_t[h] (x) B_t     (P, N)
    y_t[h] = S_t . C_t + d[h] * x_t[h]

The dual form does the same mathematics as matrix products a chunk of ``Q``
positions at a time.  With ``s_t`` the running sum of ``dt * a`` inside the
chunk::

    Y_diag = (C B^T o L o dt) x            L[t, r] = exp(s_t - s_r), r <= t
    T      = (x o exp(s_end - s) dt)^T B   the chunk's own state
    S_end  = exp(s_end) S_prev + T         L / Q sequential steps
    y      = Y_diag + exp(s) o (C S_prev^T) + d x

The forward keeps the state at every chunk's start (``L / Q`` of them) and
never the ``(H, Q, Q)`` decay matrices of more than one chunk; the custom
VJP walks the chunks backwards from those states.  State, decay sums and
accumulation are float32 whatever the operands' dtype; the matrix products
take their operands in ``x``'s dtype.

Two forms of the same two passes (``impl``):

* ``"xla"``: array operations, one ``lax.scan`` step a chunk; the backward
  is the chunk's own vector-Jacobian product, made from the chunk's start
  state.  The path of the CPU tests and of any shape the kernels do not take.
* ``"pallas"``: two TPU kernels over (batch, chunk, block of 8 heads), the
  chunks in sequence and the head blocks innermost.  ``C B^T`` is made once
  a chunk and shared by the head blocks; every head's decay matrix lives
  and dies in VMEM; the state is carried in VMEM.  What varies along a
  chunk's positions per head (``s``, ``dt``) comes in row form, ``(heads,
  Q)`` with the positions on the lanes; the column form the row scalings
  need is one padded transpose a cell.  The backward kernel walks the chunks
  from the last to the first carrying the state's gradient, and hands back
  the gradients of ``s`` and ``dt`` in row form; the reverse running sum
  that turns the first into those of ``dt`` and ``a`` is array work on
  ``(L, H)`` floats outside.  ``impl=None`` takes the kernels on a TPU
  backend where the shape allows, and ``"xla"`` elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret
from .selective_scan import _chunked, _unchunked

__all__ = ["ssd_scan"]

_LANES = 128
_HEADS = 8                      # heads a kernel's grid cell takes
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_NEG = -1e30


def _tril(q):
    return lax.broadcasted_iota(jnp.int32, (q, q), 1) <= \
        lax.broadcasted_iota(jnp.int32, (q, q), 0)


# ---------------------------------------------------------------------------
# array form
# ---------------------------------------------------------------------------

def _chunk_step(state, x, dt, a, bm, cm, d):
    """One chunk.  state (B, H, P, N) float32; x (B, Q, H, P); dt (B, Q, H)
    float32; bm, cm (B, Q, N).  Returns (the state at the chunk's end, y)."""
    dd, f32 = x.dtype, jnp.float32
    s = jnp.cumsum(dt * a, axis=1).swapaxes(1, 2)            # (B, H, Q)
    dtr = dt.swapaxes(1, 2)
    decay = jnp.exp(jnp.where(_tril(s.shape[-1]),
                              s[..., :, None] - s[..., None, :], _NEG))
    g = jnp.einsum("btn,brn->btr", cm, bm, preferred_element_type=f32)
    m = g[:, None] * decay * dtr[:, :, None, :]              # (B, H, Q, Q)
    y = jnp.einsum("bhtr,brhp->bthp", m.astype(dd), x,
                   preferred_element_type=f32)
    y += jnp.einsum("btn,bhpn->bthp", cm, state.astype(dd),
                    preferred_element_type=f32) \
        * jnp.exp(s).swapaxes(1, 2)[..., None]
    y += d[:, None] * x
    w = (jnp.exp(s[..., -1:] - s) * dtr).swapaxes(1, 2)      # (B, Q, H)
    own = jnp.einsum("brhp,brn->bhpn", (x * w[..., None]).astype(dd), bm,
                     preferred_element_type=f32)
    return jnp.exp(s[..., -1])[..., None, None] * state + own, y


def _forward(x, dt, a, bm, cm, d, chunk):
    """Padded inputs (L a multiple of chunk).  Returns (y, the state at each
    chunk's start (nc, B, H, P, N))."""
    b, l, h, p = x.shape
    nc = l // chunk

    def one(state, inp):
        new, y = _chunk_step(state, *inp[:2], a, *inp[2:], d)
        return new, (y, state)

    _, (y, starts) = lax.scan(
        one, jnp.zeros((b, h, p, bm.shape[-1]), jnp.float32),
        tuple(_chunked(v, nc, chunk) for v in (x, dt, bm, cm)))
    return _unchunked(y).astype(x.dtype), starts


def _backward(x, dt, a, bm, cm, d, dy, starts, chunk):
    nc = x.shape[1] // chunk
    dy = dy.astype(jnp.float32)

    def one(carry, inp):
        g_state, g_a, g_d = carry
        x_c, dt_c, b_c, c_c, dy_c, s0 = inp
        _, vjp = jax.vjp(_chunk_step, s0, x_c, dt_c, a, b_c, c_c, d)
        g_s0, g_x, g_dt, ga, g_b, g_c, gd = vjp((g_state, dy_c))
        return (g_s0, g_a + ga, g_d + gd), (g_x, g_dt, g_b, g_c)

    zero = jnp.zeros_like(a)
    (_, g_a, g_d), out = lax.scan(
        one, (jnp.zeros_like(starts[0]), zero, zero),
        tuple(_chunked(v, nc, chunk) for v in (x, dt, bm, cm, dy))
        + (starts,), reverse=True)
    g_x, g_dt, g_b, g_c = (_unchunked(v) for v in out)
    return g_x, g_dt, g_a, g_b, g_c, g_d


# ---------------------------------------------------------------------------
# the same two passes as TPU kernels
# ---------------------------------------------------------------------------

def _cols(rows):
    """(heads, Q) -> (Q, 128): column ``i`` is row ``i``.  Padded to whole
    tiles, which is the transpose the chip has."""
    hb, q = rows.shape
    return jnp.concatenate(
        [rows, jnp.zeros((_LANES - hb, q), rows.dtype)], axis=0).T


def _put_col(acc, i, col):
    """``acc`` (Q, 128) with column ``i`` raised by ``col`` (Q, 1)."""
    lane = lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    return acc + jnp.where(lane == i, col, 0.0)


def _decay(sc, sr, q):
    """L[t, r] = exp(s_t - s_r) at and below the diagonal, 0 above."""
    return jnp.exp(jnp.where(_tril(q), sc - sr, _NEG))


def _fwd_kernel(d_ref, end_ref, x_ref, b_ref, c_ref, s_ref, dt_ref, y_ref,
                st_ref, state_ref, g_ref, xw_ref, *, hb, p):
    """One (batch, chunk, head block) cell.  ``s_ref``/``dt_ref`` (1, 1, hb,
    Q): the running decay sums and the steps, positions on the lanes;
    ``end_ref`` (H,) scalars: the chunk's whole decay, exp(s_end);
    ``st_ref`` gets the states this chunk starts from; ``state_ref`` (H, P,
    N) carries them to the next chunk; ``g_ref`` holds the chunk's C B^T;
    ``xw_ref`` (Q, hb P) gathers the heads' weighted inputs.  The two
    products that ``B`` and ``C`` share over the heads (the readout of the
    carried state, the chunk's own state) are made for the whole block at
    once; only the product with the head's own decay matrix is a head's."""
    k, j = pl.program_id(1), pl.program_id(2)
    f32, dd = jnp.float32, x_ref.dtype
    q = x_ref.shape[1]
    n = b_ref.shape[2]
    heads = pl.ds(j * hb, hb)

    @pl.when(k == 0)
    def _():
        state_ref[heads] = jnp.zeros((hb,) + state_ref.shape[1:], f32)

    @pl.when(j == 0)
    def _():
        g_ref[...] = lax.dot_general(c_ref[0], b_ref[0], _NT,
                                     preferred_element_type=f32)

    srow, dtrow = s_ref[0, 0], dt_ref[0, 0]
    scol = _cols(srow)
    wcol = _cols(jnp.exp(srow[:, q - 1:q] - srow) * dtrow)
    g, bm, cm = g_ref[...], b_ref[0], c_ref[0]
    prev = state_ref[heads]                                  # (hb, P, N)
    st_ref[0, 0] = prev
    carried = lax.dot_general(cm, prev.reshape(hb * p, n).astype(dd), _NT,
                              preferred_element_type=f32)    # (Q, hb P)
    for i in range(hb):
        lanes = slice(i * p, (i + 1) * p)
        xi = x_ref[0, :, lanes]
        sc = scol[:, i:i + 1]
        m = g * _decay(sc, srow[i:i + 1], q) * dtrow[i:i + 1]
        y = lax.dot_general(m.astype(dd), xi, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)
        y += jnp.exp(sc) * carried[:, lanes] + d_ref[j * hb + i] * xi
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        xw_ref[:, lanes] = (xi * wcol[:, i:i + 1]).astype(dd)
    own = lax.dot_general(xw_ref[...], bm, _TN,
                          preferred_element_type=f32).reshape(hb, p, n)
    for i in range(hb):
        state_ref[j * hb + i] = end_ref[j * hb + i] * prev[i] + own[i]


def _bwd_kernel(d_ref, end_ref, x_ref, dy_ref, b_ref, c_ref, s_ref, dt_ref,
                st_ref, dx_ref, db_ref, dc_ref, ds_ref, ddt_ref, dd_ref,
                gstate_ref, g_ref, dg_ref, xw_ref, ldy_ref, *, hb, p):
    """One (batch, chunk counted from the end, head block) cell.
    ``gstate_ref`` (H, P, N) carries the gradient of the state at the
    chunk's end to the chunk before; ``dg_ref`` gathers the head blocks'
    gradient of C B^T; ``db_ref``/``dc_ref`` stay resident over the head
    blocks and gather their sums; ``ds_ref``/``ddt_ref`` (1, 1, hb, Q) get
    the gradients of the decay sums and of the steps where they enter
    directly, ``dd_ref`` (1, 1, hb, P) the skip's, summed over positions;
    ``xw_ref``/``ldy_ref`` (Q, hb P) gather the heads' weighted inputs and
    decayed output gradients for the block's shared products."""
    k, j = pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(2) - 1
    f32, dd = jnp.float32, x_ref.dtype
    q = x_ref.shape[1]
    n = b_ref.shape[2]
    heads = pl.ds(j * hb, hb)
    plain = (((1,), (0,)), ((), ()))

    @pl.when(k == 0)
    def _():
        gstate_ref[heads] = jnp.zeros((hb,) + gstate_ref.shape[1:], f32)

    @pl.when(j == 0)
    def _():
        g_ref[...] = lax.dot_general(c_ref[0], b_ref[0], _NT,
                                     preferred_element_type=f32)
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    srow, dtrow = s_ref[0, 0], dt_ref[0, 0]
    erow = jnp.exp(srow[:, q - 1:q] - srow)  # decay from r to the chunk's end
    scol, ecol, dtcol = _cols(srow), _cols(erow), _cols(dtrow)
    g, bm, cm = g_ref[...], b_ref[0], c_ref[0]
    prev, gend = st_ref[0, 0], gstate_ref[heads]             # (hb, P, N)
    prev2, gend2 = (v.reshape(hb * p, n).astype(dd) for v in (prev, gend))
    bg_all = lax.dot_general(bm, gend2, _NT, preferred_element_type=f32)
    cs_all = lax.dot_general(cm, prev2, _NT, preferred_element_type=f32)
    is_end = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    ds_cols = jnp.zeros((q, _LANES), f32)
    ddt_cols = jnp.zeros((q, _LANES), f32)
    ds_rows, ddt_rows = [], []
    for i in range(hb):
        lanes = slice(i * p, (i + 1) * p)
        xi, dyi = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        xf, dyf = xi.astype(f32), dyi.astype(f32)
        bg, cs = bg_all[:, lanes], cs_all[:, lanes]   # B gend^T, C prev^T
        sc, ec, dtc = (v[:, i:i + 1] for v in (scol, ecol, dtcol))
        dtr = dtrow[i:i + 1]
        decay = _decay(sc, srow[i:i + 1], q)
        gl = g * decay
        m = gl * dtr
        lam, a_end = jnp.exp(sc), end_ref[j * hb + i]
        w = ec * dtc                                         # (Q, 1)
        dm = lax.dot_general(dyi, xi, _NT, preferred_element_type=f32)
        dx = lax.dot_general(m.astype(dd), dyi, _TN,
                             preferred_element_type=f32)
        dx_ref[0, :, lanes] = (
            dx + w * bg + d_ref[j * hb + i] * dyf).astype(dx_ref.dtype)
        ldy_ref[:, lanes] = (lam * dyf).astype(dd)
        xw_ref[:, lanes] = (w * xf).astype(dd)
        dg_ref[...] += dm * decay * dtr
        # the decay sums and the steps
        e = dm * m                                # d loss / d (s_t - s_r)
        dw = jnp.sum(xf * bg, axis=1, keepdims=True)         # (Q, 1)
        dlam = jnp.sum(dyf * cs, axis=1, keepdims=True)
        at_end = jnp.sum(dw * w, axis=0, keepdims=True) \
            + a_end * jnp.sum(gend[i] * prev[i], keepdims=True)
        ds_cols = _put_col(
            ds_cols, i, jnp.sum(e, axis=1, keepdims=True) + lam * dlam
            - dw * w + jnp.where(is_end, at_end, 0.0))
        ddt_cols = _put_col(ddt_cols, i, dw * ec)
        ds_rows.append(-jnp.sum(e, axis=0, keepdims=True))
        ddt_rows.append(jnp.sum(dm * gl, axis=0, keepdims=True))
        dd_ref[0, 0, i:i + 1] = jnp.sum(dyf * xf, axis=0, keepdims=True)
    ds_ref[0, 0] = jnp.concatenate(ds_rows, axis=0) + ds_cols.T[:hb]
    ddt_ref[0, 0] = jnp.concatenate(ddt_rows, axis=0) + ddt_cols.T[:hb]
    ldy, xw = ldy_ref[...], xw_ref[...]
    dc_ref[0] += lax.dot_general(ldy, prev2, plain,
                                 preferred_element_type=f32)
    db_ref[0] += lax.dot_general(xw, gend2, plain,
                                 preferred_element_type=f32)
    into = lax.dot_general(ldy, cm, _TN,
                           preferred_element_type=f32).reshape(hb, p, n)
    for i in range(hb):
        gstate_ref[j * hb + i] = end_ref[j * hb + i] * gend[i] + into[i]

    @pl.when(j == last)
    def _():
        dg = dg_ref[...].astype(dd)
        dc_ref[0] += lax.dot_general(dg, bm, plain,
                                     preferred_element_type=f32)
        db_ref[0] += lax.dot_general(dg, cm, _TN,
                                     preferred_element_type=f32)


def _head_block(h: int) -> int:
    return _HEADS if h % _HEADS == 0 else h


def _smem_block(h: int) -> int:
    """A 1-D float32 array comes to a kernel in tiles of 1024."""
    return -(-h // 1024) * 1024


def _rows(dt, a, chunk):
    """The running decay sums and the steps in row form, (B, nc, H, Q), and
    each chunk's whole decay exp(s_end) for SMEM: flat, a chunk's heads
    padded to a whole block of ``_smem_block(H)``."""
    b, l, h = dt.shape
    dt_c = dt.reshape(b, l // chunk, chunk, h)
    s = jnp.cumsum(dt_c * a, axis=2)
    ends = jnp.pad(jnp.exp(s[:, :, -1]),
                   ((0, 0), (0, 0), (0, _smem_block(h) - h)))
    return s.swapaxes(2, 3), dt_c.swapaxes(2, 3), ends.reshape(-1)


def _forward_pallas(x, dt, a, bm, cm, d, chunk, interpret):
    b, l, h, p = x.shape
    n, nc, hb = bm.shape[-1], l // chunk, _head_block(h)
    srow, dtrow, ends = _rows(dt, a, chunk)
    wide = pl.BlockSpec((1, chunk, hb * p), lambda i, k, j: (i, k, j))
    bc = pl.BlockSpec((1, chunk, n), lambda i, k, j: (i, k, 0))
    row = pl.BlockSpec((1, 1, hb, chunk), lambda i, k, j: (i, k, j, 0))
    y, starts = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, p=p),
        grid=(b, nc, h // hb),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((_smem_block(h),),
                               lambda i, k, j: (i * nc + k,),
                               memory_space=pltpu.SMEM),
                  wide, bc, bc, row, row],
        out_specs=[wide, pl.BlockSpec((1, 1, hb, p, n),
                                      lambda i, k, j: (i, k, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, l, h * p), x.dtype),
                   jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h, p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, hb * p), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(d, ends, x.reshape(b, l, h * p), bm, cm, srow, dtrow)
    return y.reshape(b, l, h, p), starts


def _backward_pallas(x, dt, a, bm, cm, d, dy, starts, chunk, interpret):
    b, l, h, p = x.shape
    n, nc, hb = bm.shape[-1], l // chunk, _head_block(h)
    f32 = jnp.float32
    srow, dtrow, ends = _rows(dt, a, chunk)
    wide = pl.BlockSpec((1, chunk, hb * p),
                        lambda i, k, j: (i, nc - 1 - k, j))
    bc = pl.BlockSpec((1, chunk, n), lambda i, k, j: (i, nc - 1 - k, 0))
    row = pl.BlockSpec((1, 1, hb, chunk),
                       lambda i, k, j: (i, nc - 1 - k, j, 0))
    rows = jax.ShapeDtypeStruct((b, nc, h, chunk), f32)
    flat = jax.ShapeDtypeStruct((b, l, n), f32)
    dx, db, dc, ds, ddt, dd_ = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p),
        grid=(b, nc, h // hb),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((_smem_block(h),),
                               lambda i, k, j: (i * nc + nc - 1 - k,),
                               memory_space=pltpu.SMEM),
                  wide, wide, bc, bc, row, row,
                  pl.BlockSpec((1, 1, hb, p, n),
                               lambda i, k, j: (i, nc - 1 - k, j, 0, 0))],
        out_specs=[wide, bc, bc, row, row,
                   pl.BlockSpec((1, 1, hb, p),
                                lambda i, k, j: (i, nc - 1 - k, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, l, h * p), x.dtype), flat, flat,
                   rows, rows, jax.ShapeDtypeStruct((b, nc, h, p), f32)],
        scratch_shapes=[pltpu.VMEM((h, p, n), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, hb * p), x.dtype),
                        pltpu.VMEM((chunk, hb * p), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(d, ends, x.reshape(b, l, h * p), dy.reshape(b, l, h * p), bm, cm,
      srow, dtrow, starts)
    # s is a running sum of dt * a inside the chunk: its gradient runs back
    g_da = jnp.flip(jnp.cumsum(jnp.flip(ds, -1), -1), -1)
    g_dt = (ddt + g_da * a[:, None]).swapaxes(2, 3).reshape(b, l, h)
    return (dx.reshape(b, l, h, p), g_dt, jnp.sum(g_da * dtrow, (0, 1, 3)),
            db, dc, jnp.sum(dd_, (0, 1, 3)))


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ssd(x, dt, a, bm, cm, d, chunk, pallas, interpret):
    return _ssd_fwd(x, dt, a, bm, cm, d, chunk, pallas, interpret)[0]


def _ssd_fwd(x, dt, a, bm, cm, d, chunk, pallas, interpret):
    y, starts = (_forward_pallas(x, dt, a, bm, cm, d, chunk, interpret)
                 if pallas else _forward(x, dt, a, bm, cm, d, chunk))
    return y, (x, dt, a, bm, cm, d, starts)


def _ssd_bwd(chunk, pallas, interpret, res, dy):
    x, dt, a, bm, cm, d, starts = res
    out = _backward_pallas(x, dt, a, bm, cm, d, dy, starts, chunk,
                           interpret) if pallas \
        else _backward(x, dt, a, bm, cm, d, dy, starts, chunk)
    return tuple(g.astype(v.dtype) for g, v in zip(out, res))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, a, b, c, d, chunk: int = 256,
             impl: Optional[str] = None, interpret: Optional[bool] = None):
    """``x`` (batch, L, H, P); ``dt`` (batch, L, H), positive; ``a`` (H,),
    negative; ``b``, ``c`` (batch, L, N), shared by the heads; ``d`` (H,).
    Returns ``y`` (batch, L, H, P) in ``x``'s dtype.  ``L`` need not be a
    multiple of ``chunk``: the tail is padded with ``dt = 0``, which leaves
    the state as it was.  ``impl``: ``"xla"``, ``"pallas"`` (``interpret``
    as for the other kernels: compiled on a TPU, interpreted elsewhere) or
    None, which takes the kernels on a TPU backend where whole tiles hold
    the shape (P x 8 heads and N multiples of 128, the chunk of 128)."""
    l, h, p = x.shape[1:]
    # a row shorter than the chunk is one chunk: of its own length in array
    # form, of whole tiles (padded) for the kernels
    short, tiled = min(chunk, l), min(chunk, -(-l // _LANES) * _LANES)
    tiles = (_head_block(h) * p) % _LANES == 0 \
        and b.shape[-1] % _LANES == 0 and tiled % _LANES == 0
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" and tiles \
            else "xla"
    assert impl in ("xla", "pallas"), impl
    pallas = impl == "pallas"
    chunk = short
    if pallas:
        interpret = resolve_interpret(interpret, "ssd_scan")
        assert interpret or tiles, (x.shape, b.shape, chunk)
        chunk = short if interpret else tiled
    pad = -l % chunk
    f32 = lambda v: v.astype(jnp.float32)                     # noqa: E731

    def padded(v):
        return jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) \
            if pad else v

    y = _ssd(padded(x), padded(f32(dt)), f32(a), padded(b.astype(x.dtype)),
             padded(c.astype(x.dtype)), f32(d), chunk, pallas,
             bool(interpret))
    return y[:, :l].astype(x.dtype)
