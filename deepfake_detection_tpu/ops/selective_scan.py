"""Selective scan (Mamba-1 recurrence), chunked over the sequence.

For every channel ``c`` and state index ``n``::

    s_t = exp(delta_t[c] * A[c, n]) * s_{t-1} + delta_t[c] * B_t[n] * u_t[c]
    y_t[c] = sum_n C_t[n] * s_t[c, n] + D[c] * u_t[c]

The naive scan keeps ``L x D x N`` float32 states for the backward pass
(5.4 GB at L = 16,384, D = 5120, N = 16).  Here the forward keeps only the
state at every chunk's start (``L / chunk`` of them) and the custom VJP
walks the chunks backwards, rebuilding one chunk's ``chunk x N x D`` states
at a time.  State and accumulation are float32 whatever the inputs' dtype;
outputs and input gradients come back in the inputs' dtypes.

Two forms of the same two passes (``impl``):

* ``"lax"``: the sequential part is two ``lax.scan``s over the chunk's steps
  (states forward, their gradients backward, both carrying one ``(N, D)``
  float32 state), and the reductions that give the gradients of ``u``,
  ``delta``, ``A``, ``B``, ``C`` and ``D`` are whole-chunk array operations.
  The path of the CPU tests, and of any shape the kernels do not take.
* ``"pallas"``: two TPU kernels.  The channels are cut into blocks of 1024
  laid out as dense (8, 128) tiles, so that the state of one block is ``N``
  vector registers and every step is elementwise work on whole tiles:
  ``B_t[n]`` and ``C_t[n]`` are scalars read from SMEM, the loop over ``n``
  is unrolled, and the state never leaves the registers inside a chunk.  The
  forward kernel walks (batch, channel block, chunk) with the chunks
  innermost, carrying the state in VMEM, and writes ``y`` and each chunk's
  first state.  The backward kernel walks (batch, chunk backwards, channel
  block): it rebuilds the chunk's states into VMEM, then steps backwards
  through the chunk with the states' gradient in registers, writing the
  gradients of ``u`` and ``delta`` as tiles, accumulating that of ``A`` per
  channel block in VMEM, and those of ``B`` and ``C`` -- sums over all
  channels -- as per-lane partial sums over the channel blocks (the last
  128-lane sum is taken outside).  ``impl=None`` takes the kernels on a TPU
  backend when the channels are a multiple of 1024, and ``"lax"`` elsewhere.
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

__all__ = ["selective_scan"]

_UNROLL = 8
_SUB, _LANES = 8, 128
_BLOCK = _SUB * _LANES          # channels a kernel's grid cell takes


def _chunked(x, nc, chunk):
    return x.reshape((x.shape[0], nc, chunk) + x.shape[2:]).swapaxes(0, 1)


def _unchunked(x):
    x = x.swapaxes(0, 1)
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


def _chunk_states(s0, u, delta, At, Bm):
    """All states of one chunk.  s0 (B, N, D); u, delta (B, T, D); At (N, D);
    Bm (B, T, N).  Returns (s_T, states (T, B, N, D))."""
    def step(s, inp):
        u_t, d_t, b_t = inp                         # (B, D), (B, D), (B, N)
        a = jnp.exp(d_t[:, None, :] * At)
        s = a * s + (d_t * u_t)[:, None, :] * b_t[:, :, None]
        return s, s
    return lax.scan(step, s0, (u.swapaxes(0, 1), delta.swapaxes(0, 1),
                               Bm.swapaxes(0, 1)), unroll=_UNROLL)


def _forward(u, delta, A, Bm, Cm, Dv, chunk):
    """Padded, float32 inputs (L a multiple of chunk).  Returns (y, the
    state at each chunk's start)."""
    b, l, d = u.shape
    n = A.shape[1]
    nc = l // chunk
    At = A.T

    def one_chunk(s0, inp):
        u_c, d_c, b_c, c_c = inp

        def step(s, x):
            u_t, d_t, b_t, c_t = x
            a = jnp.exp(d_t[:, None, :] * At)
            s = a * s + (d_t * u_t)[:, None, :] * b_t[:, :, None]
            return s, jnp.sum(s * c_t[:, :, None], axis=1)
        s1, y = lax.scan(step, s0, tuple(x.swapaxes(0, 1) for x in
                                         (u_c, d_c, b_c, c_c)),
                         unroll=_UNROLL)
        return s1, (y.swapaxes(0, 1), s0)

    s0 = jnp.zeros((b, n, d), jnp.float32)
    _, (y, starts) = lax.scan(one_chunk, s0, tuple(
        _chunked(x, nc, chunk) for x in (u, delta, Bm, Cm)))
    return _unchunked(y) + u * Dv, starts


def _backward(u, delta, A, Bm, Cm, dy, starts, chunk):
    """Gradients of ``u`` (without the skip's), ``delta``, ``A``, ``B`` and
    ``C`` from the states at the chunks' starts."""
    b, l, d = u.shape
    nc = l // chunk
    At = A.T

    def one_chunk(carry, inp):
        g_next, dAt = carry            # d loss / d (state at chunk's end)
        u_c, d_c, b_c, c_c, dy_c, s0 = inp
        _, states = _chunk_states(s0, u_c, d_c, At, b_c)   # (T, B, N, D)
        prev = jnp.concatenate([s0[None], states[:-1]], axis=0)
        d_t = d_c.swapaxes(0, 1)                           # (T, B, D)
        a = jnp.exp(d_t[:, :, None, :] * At)               # (T, B, N, D)
        dy_t, c_t = dy_c.swapaxes(0, 1), c_c.swapaxes(0, 1)

        def step(g, x):
            a_next, dy_s, c_s = x
            g = g * a_next + dy_s[:, None, :] * c_s[:, :, None]
            return g, g
        # g_t = C_t (x) dy_t + a_{t+1} * g_{t+1}; past the chunk's end the
        # factor is folded into the carry, so it enters with factor one
        a_shift = jnp.concatenate([a[1:], jnp.ones_like(a[:1])], axis=0)
        g_first, g = lax.scan(step, g_next, (a_shift, dy_t, c_t),
                              reverse=True, unroll=_UNROLL)
        ga = g * a * prev                                   # (T, B, N, D)
        du_t = d_t * u_c.swapaxes(0, 1)                     # delta * u
        b_t = b_c.swapaxes(0, 1)
        # products and sums, not dots: float32 on the vector unit
        gb = jnp.sum(g * b_t[..., None], axis=2)
        d_u = gb * d_t
        d_delta = jnp.sum(ga * At, axis=2) + gb * u_c.swapaxes(0, 1)
        d_b = jnp.sum(g * du_t[:, :, None, :], axis=3)
        d_c = jnp.sum(states * dy_t[:, :, None, :], axis=3)
        dAt = dAt + jnp.sum(ga * d_t[:, :, None, :], axis=(0, 1))
        # the carry for the chunk before: gradient of this chunk's start
        g_prev = g_first * a[0]
        return (g_prev, dAt), tuple(x.swapaxes(0, 1)
                                    for x in (d_u, d_delta, d_b, d_c))

    n = A.shape[1]
    init = (jnp.zeros((b, n, d), jnp.float32), jnp.zeros((n, d), jnp.float32))
    (_, dAt), (d_u, d_delta, d_b, d_c) = lax.scan(
        one_chunk, init, tuple(_chunked(x, nc, chunk) for x in
                               (u, delta, Bm, Cm, dy)) + (starts,),
        reverse=True)
    return (_unchunked(d_u), _unchunked(d_delta), dAt.T, _unchunked(d_b),
            _unchunked(d_c))


# ---------------------------------------------------------------------------
# the same two passes as TPU kernels
# ---------------------------------------------------------------------------

def _advance(t, s, b_ref, u_ref, dl_ref, a_ref):
    """One step of the recurrence on a channel block: ``s`` is the state as
    a tuple of N (8, 128) tiles, one a state index."""
    d = dl_ref[0, t]
    du = d * u_ref[0, t]
    n_state = len(s)
    return tuple(jnp.exp(d * a_ref[n]) * s[n] + du * b_ref[t * n_state + n]
                 for n in range(n_state))


def _fwd_kernel(b_ref, c_ref, u_ref, dl_ref, a_ref, y_ref, s0_ref, s_ref,
                *, chunk, n_state):
    """One (batch, channel block, chunk) cell.  ``b_ref``/``c_ref``: the
    chunk's ``chunk x N`` scalars in SMEM; ``u_ref``/``dl_ref``/``y_ref``:
    ``(1, chunk, 8, 128)`` tiles; ``a_ref`` ``(N, 8, 128)``; ``s0_ref`` gets
    the state this chunk starts from; ``s_ref`` carries it to the next."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    s0_ref[0, 0] = s_ref[...]

    def step(t, s):
        s = _advance(t, s, b_ref, u_ref, dl_ref, a_ref)
        y_ref[0, t] = sum(s[n] * c_ref[t * n_state + n]
                          for n in range(n_state))
        return s

    s = lax.fori_loop(0, chunk, step,
                      tuple(s_ref[n] for n in range(n_state)))
    for n in range(n_state):
        s_ref[n] = s[n]


def _bwd_kernel(b_ref, c_ref, u_ref, dl_ref, dy_ref, a_ref, s0_ref,
                du_ref, ddl_ref, dbp_ref, dcp_ref, da_ref,
                st_ref, g_ref, acc_ref, *, chunk, n_state):
    """One (batch, chunk counted from the end, channel block) cell.
    ``st_ref[t]`` holds the state before step ``t`` of this chunk;
    ``g_ref[j]`` carries block ``j``'s gradient of the state at the chunk's
    start to the chunk before, ``acc_ref[j]`` its gradient of ``A``.
    ``dbp_ref``/``dcp_ref`` ``(1, chunk, N, 128)`` gather the channel
    blocks' per-lane partial sums of the gradients of ``B`` and ``C``."""
    k, j = pl.program_id(1), pl.program_id(2)
    zeros = jnp.zeros((_SUB, _LANES), jnp.float32)

    @pl.when(k == 0)
    def _():
        g_ref[j] = jnp.zeros(g_ref.shape[1:], jnp.float32)
        acc_ref[j] = jnp.zeros(acc_ref.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _():
        dbp_ref[...] = jnp.zeros_like(dbp_ref)
        dcp_ref[...] = jnp.zeros_like(dcp_ref)

    st_ref[0] = s0_ref[0, 0]

    def forward(t, s):
        s = _advance(t, s, b_ref, u_ref, dl_ref, a_ref)
        for n in range(n_state):
            st_ref[t + 1, n] = s[n]
        return s

    lax.fori_loop(0, chunk, forward,
                  tuple(s0_ref[0, 0, n] for n in range(n_state)))

    def backward(i, g):
        # g[n]: a_{t+1} * (gradient of s_{t+1}), zero past the sequence's end
        t = chunk - 1 - i
        d, u, dy = dl_ref[0, t], u_ref[0, t], dy_ref[0, t]
        du = d * u
        gb, ga_a = zeros, zeros
        rows_b, rows_c, out = [], [], []
        for n in range(n_state):
            an = a_ref[n]
            gn = dy * c_ref[t * n_state + n] + g[n]
            a = jnp.exp(d * an)
            ga = gn * a * st_ref[t, n]
            ga_a = ga_a + ga * an
            gb = gb + gn * b_ref[t * n_state + n]
            acc_ref[j, n] += ga * d
            rows_b.append(jnp.sum(gn * du, axis=0, keepdims=True))
            rows_c.append(jnp.sum(st_ref[t + 1, n] * dy, axis=0,
                                  keepdims=True))
            out.append(a * gn)
        ddl_ref[0, t] = ga_a + gb * u
        du_ref[0, t] = gb * d
        dbp_ref[0, t] += jnp.concatenate(rows_b, axis=0)
        dcp_ref[0, t] += jnp.concatenate(rows_c, axis=0)
        return tuple(out)

    g = lax.fori_loop(0, chunk, backward,
                      tuple(g_ref[j, n] for n in range(n_state)))
    for n in range(n_state):
        g_ref[j, n] = g[n]
    da_ref[0] = acc_ref[j]


def _tiles(x):
    """(B, L, D) -> (B, L, D / 128, 128): a block of 8 rows of it is 1024
    channels as one dense tile."""
    return x.reshape(x.shape[:-1] + (x.shape[-1] // _LANES, _LANES))


def _forward_pallas(u, delta, A, Bm, Cm, Dv, chunk, interpret):
    b, l, d = u.shape
    n = A.shape[1]
    nd, nc = d // _BLOCK, l // chunk
    smem = lambda: pl.BlockSpec(                                  # noqa: E731
        (chunk * n,), lambda i, j, k: (i * nc + k,), memory_space=pltpu.SMEM)
    tile = lambda: pl.BlockSpec(                                  # noqa: E731
        (1, chunk, _SUB, _LANES), lambda i, j, k: (i, k, j, 0))
    y, starts = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, n_state=n),
        grid=(b, nd, nc),
        in_specs=[smem(), smem(), tile(), tile(),
                  pl.BlockSpec((n, _SUB, _LANES), lambda i, j, k: (0, j, 0))],
        out_specs=[tile(), pl.BlockSpec((1, 1, n, _SUB, _LANES),
                                        lambda i, j, k: (i, k, 0, j, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, d // _LANES, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, n, d // _LANES, _LANES),
                                 jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, _SUB, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(Bm.reshape(-1), Cm.reshape(-1), _tiles(u), _tiles(delta),
      _tiles(A.T))
    return y.reshape(b, l, d) + u * Dv, starts


def _backward_pallas(u, delta, A, Bm, Cm, dy, starts, chunk, interpret):
    b, l, d = u.shape
    n = A.shape[1]
    nd, nc = d // _BLOCK, l // chunk
    smem = lambda: pl.BlockSpec(                                  # noqa: E731
        (chunk * n,), lambda i, k, j: (i * nc + nc - 1 - k,),
        memory_space=pltpu.SMEM)
    tile = lambda: pl.BlockSpec(                                  # noqa: E731
        (1, chunk, _SUB, _LANES), lambda i, k, j: (i, nc - 1 - k, j, 0))
    part = lambda: pl.BlockSpec(                                  # noqa: E731
        (1, chunk, n, _LANES), lambda i, k, j: (i, nc - 1 - k, 0, 0))
    tiles = jax.ShapeDtypeStruct((b, l, d // _LANES, _LANES), jnp.float32)
    parts = jax.ShapeDtypeStruct((b, l, n, _LANES), jnp.float32)
    carry = pltpu.VMEM((nd, n, _SUB, _LANES), jnp.float32)
    d_u, d_delta, d_b, d_c, d_a = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, n_state=n),
        grid=(b, nc, nd),
        in_specs=[smem(), smem(), tile(), tile(), tile(),
                  pl.BlockSpec((n, _SUB, _LANES), lambda i, k, j: (0, j, 0)),
                  pl.BlockSpec((1, 1, n, _SUB, _LANES),
                               lambda i, k, j: (i, nc - 1 - k, 0, j, 0))],
        out_specs=[tile(), tile(), part(), part(),
                   pl.BlockSpec((1, n, _SUB, _LANES),
                                lambda i, k, j: (i, 0, j, 0))],
        out_shape=[tiles, tiles, parts, parts, jax.ShapeDtypeStruct(
            (b, n, d // _LANES, _LANES), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((chunk + 1, n, _SUB, _LANES), jnp.float32),
            carry, carry],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            # the chunk's states: (chunk + 1) x N x 4 KB (8.3 MB at 128)
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(Bm.reshape(-1), Cm.reshape(-1), _tiles(u), _tiles(delta), _tiles(dy),
      _tiles(A.T), starts)
    return (d_u.reshape(b, l, d), d_delta.reshape(b, l, d),
            d_a.sum(axis=0).reshape(n, d).T, d_b.sum(axis=-1),
            d_c.sum(axis=-1))


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(u, delta, A, Bm, Cm, Dv, chunk, pallas, interpret):
    if pallas:
        return _forward_pallas(u, delta, A, Bm, Cm, Dv, chunk, interpret)[0]
    return _forward(u, delta, A, Bm, Cm, Dv, chunk)[0]


def _scan_fwd(u, delta, A, Bm, Cm, Dv, chunk, pallas, interpret):
    y, starts = _forward_pallas(u, delta, A, Bm, Cm, Dv, chunk, interpret) \
        if pallas else _forward(u, delta, A, Bm, Cm, Dv, chunk)
    return y, (u, delta, A, Bm, Cm, Dv, starts)


def _scan_bwd(chunk, pallas, interpret, res, dy):
    u, delta, A, Bm, Cm, Dv, starts = res
    d_u, d_delta, d_a, d_b, d_c = _backward_pallas(
        u, delta, A, Bm, Cm, dy, starts, chunk, interpret) if pallas \
        else _backward(u, delta, A, Bm, Cm, dy, starts, chunk)
    return (d_u + dy * Dv, d_delta, d_a, d_b, d_c,
            jnp.sum(dy * u, axis=(0, 1)))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, delta, A, B, C, D, chunk: int = 256,
                   impl: Optional[str] = None,
                   interpret: Optional[bool] = None):
    """``u``, ``delta`` (batch, L, D); ``A`` (D, N), negative; ``B``, ``C``
    (batch, L, N); ``D`` (D,).  Returns ``y`` (batch, L, D) in ``u``'s
    dtype.  ``L`` need not be a multiple of ``chunk``: the tail is padded
    with ``delta = 0``, which leaves the state as it was.  ``impl``:
    ``"lax"``, ``"pallas"`` (``D`` a multiple of 1024; ``interpret`` as for
    the other kernels: compiled on a TPU, interpreted elsewhere) or None,
    which takes the kernels on a TPU backend where the shape allows."""
    l, d = u.shape[1], u.shape[2]
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" \
            and d % _BLOCK == 0 else "lax"
    assert impl in ("lax", "pallas"), impl
    pallas = impl == "pallas"
    if pallas:
        assert d % _BLOCK == 0, f"impl='pallas' needs D % {_BLOCK} == 0: {d}"
        interpret = resolve_interpret(interpret, "selective_scan")
        chunk = -(-min(chunk, l) // _SUB) * _SUB
    else:
        chunk = min(chunk, l)
    pad = -l % chunk
    f32 = lambda x: x.astype(jnp.float32)                 # noqa: E731

    def padded(x):
        x = f32(x)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    y = _scan(padded(u), padded(delta), f32(A), padded(B), padded(C), f32(D),
              chunk, pallas, bool(interpret))
    return y[:, :l].astype(u.dtype)
