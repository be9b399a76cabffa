"""Fused depthwise-conv → scale/shift → activation as Pallas TPU kernels.

PERF.md's roofline puts the EfficientNet family within 1.5% of the bf16-VPU
ceiling: the depthwise stages are the binding term, and XLA executes each as
``dw-conv (VPU) → write HBM → read HBM → BN normalize → act → write HBM``
when the epilogue does not fuse cleanly (separate fusions around the conv).
This module collapses the whole stage into one VMEM-resident pass: the conv
accumulator never leaves VMEM between the k²-tap multiply-adds and the
per-channel affine + activation epilogue, so the stage's HBM traffic drops
to the unavoidable ``read x, write y``.

Kernel structure (same conventions as ``ops/flash_attention.py``):

* grid ``(B, C tiles, H tiles)`` with the H-tile axis innermost so Pallas
  pipelines one ``(th_in, W, Ct)`` input block at a time through VMEM.
  Depthwise halos overlap between consecutive H tiles, which plain blocked
  BlockSpecs cannot express — the input spec is an **element-offset
  window** (every dim a ``pl.Element``) over an input the wrapper has
  already padded in XLA (one pad op; XLA materializes conv padding anyway)
  and, for stride 2, split into its 2×2 polyphase components
  (``_halo_tiles``) so the kernel never needs a strided slice.
* the k² taps unroll as static Python loops of unit-stride window slices +
  multiply-accumulate on the VPU, f32 accumulation regardless of input
  dtype; the affine + activation epilogue runs on the accumulator while it
  is still VMEM-resident.
* backward is a custom VJP: ``dx`` REUSES the forward kernel (a depthwise
  transposed conv is the same kernel over the interior-dilated, re-padded
  upstream gradient with a flipped kernel), ``dw`` is a second Pallas
  reduction kernel accumulating the k²-tap correlation into VMEM scratch
  across the (B, H-tile) grid steps, and the tiny per-channel
  ``dscale``/``dbias`` reductions stay in XLA where they fuse with the
  activation-gradient elementwise pass.

The ``dw`` reduction also serves the DEFAULT path (PR 29): where
``ops/conv.py:dw_grad_impl`` says so (small batch on one TPU device, where
XLA would feed its own filter gradient a k-fold copy of the input),
``Conv2d`` computes a depthwise stage through :func:`depthwise_conv` —
XLA's convolution and input gradient untouched, ``dW`` by this kernel from
bf16 operands read once.

On non-TPU backends the kernels run under the Pallas interpreter
(``interpret=True``), which is how the CPU suite checks forward AND
gradient parity against the XLA lowering (tests/test_depthwise_pallas.py).
Outputs declare their varying-mesh-axes set from the input operand
(``_out_struct``), so the op is check_vma-safe under ``shard_map``
exactly like the flash kernels.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .conv import resolve_padding
from .flash_attention import _out_struct, _scratch, resolve_interpret

__all__ = ["fused_depthwise", "FUSED_DW_ACTS", "depthwise_conv"]

#: epilogue activations the kernel fuses; anything else runs act in XLA
FUSED_DW_ACTS = ("none", "silu", "relu")

_LANES = 128


def _vmem_spec(block_shape, index_map, element: bool = False):
    """``element=True``: every dim is a ``pl.Element`` window, so
    ``index_map`` returns element offsets, not block indices (Mosaic wants
    all dims of an operand element-indexed or none)."""
    if element:
        block_shape = tuple(pl.Element(d) for d in block_shape)
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _act_f32(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "relu":
        return lambda u: jnp.maximum(u, 0.0)
    return lambda u: u


def _act_grad_f32(name: str):
    """d act(u) / du, evaluated in f32."""
    if name == "silu":
        def g(u):
            s = jax.nn.sigmoid(u)
            return s * (1.0 + u * (1.0 - s))
        return g
    if name == "relu":
        return lambda u: (u > 0.0).astype(jnp.float32)
    return lambda u: jnp.ones_like(u)


def _to_tuple(v) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


def _explicit_pads(pad, x_shape, ks, stride: int):
    """``((lo, hi), (lo, hi))`` of ints from a :func:`resolve_padding`
    result (``'SAME'`` is TF's: the odd pixel goes to the end)."""
    if pad == "SAME":
        def _same(n, k):
            need = max((-(-n // stride) - 1) * stride + k - n, 0)
            return (need // 2, need - need // 2)
        return (_same(x_shape[1], ks[0]), _same(x_shape[2], ks[1]))
    if pad == "VALID":
        return ((0, 0), (0, 0))
    return tuple(tuple(int(p) for p in pr) for pr in pad)


def _pick_block_h(wph: int, ct: int, kh: int, stride: int,
                  ho: int, budget: int = 2 * 1024 * 1024) -> int:
    """Largest output-rows-per-tile whose f32 input halo block (all stride²
    phases) fits the VMEM budget (Pallas double-buffers, so stay well under
    the 16 MB arena)."""
    th = max(1, min(ho, 8))
    halo = (kh - 1) // stride
    while th > 1 and stride * stride * (th + halo) * wph * ct * 4 > budget:
        th -= 1
    return th


def _channel_tile(c: int) -> int:
    """Lane-friendly channel tile: full lanes when divisible, else the whole
    (padded) channel extent for small C."""
    if c % _LANES == 0:
        return _LANES
    return c


def _halo_tiles(x, pads, kh: int, kw: int, stride: int, ho: int, wo: int):
    """Phase-split, conv- and tile-padded input + its halo BlockSpec factory.

    Mosaic has neither a strided slice of a loaded value nor a strided load
    of packed (bf16) data, so a stride-``s`` conv is fed as its ``s²``
    polyphase components — ``xph[b, p·s+q, i, j] = xp[b, i·s+p, j·s+q]`` —
    and tap ``(r, c)`` becomes a unit-stride window of phase ``(r%s, c%s)``
    at offset ``(r//s, c//s)``.  Stride 1 is the single-phase case (a free
    reshape).  ``pads`` is the conv's ``((lo, hi), (lo, hi))``; it and the
    rows that keep every H tile's halo block in-bounds go on in ONE pad, in
    the operand's own dtype.

    Returns ``(xph, th_out, n_h, spec)`` with ``spec(b_of, c_of, h_of)``
    building the element-indexed input BlockSpec from grid-index pickers.
    """
    b, h, w, c = x.shape
    (ph0, _), (pw0, _) = pads
    ct = _channel_tile(c)
    wph = wo + (kw - 1) // stride
    th_out = _pick_block_h(wph, ct, kh, stride, ho)
    n_h = -(-ho // th_out)
    th_in = th_out + (kh - 1) // stride
    hph = (n_h - 1) * th_out + th_in
    # lax.pad: negative high padding crops rows/cols no tap window reaches
    xp = lax.pad(x, jnp.zeros((), x.dtype),
                 ((0, 0, 0), (ph0, hph * stride - h - ph0, 0),
                  (pw0, wph * stride - w - pw0, 0), (0, 0, 0)))
    xph = xp.reshape(b, hph, stride, wph, stride, c)
    xph = xph.transpose(0, 2, 4, 1, 3, 5).reshape(
        b, stride * stride, hph, wph, c)

    def spec(b_of, c_of, h_of):
        # a lone whole-C tile (C not a lane multiple) takes a literal 0
        # lane offset: Mosaic must prove the offset divides the 128 tiling
        # and cannot see that the channel grid index is always 0 there
        def index_map(*g):
            return (b_of(*g), 0, h_of(*g) * th_out, 0,
                    0 if ct == c else c_of(*g) * ct)
        return _vmem_spec((1, stride * stride, th_in, wph, ct), index_map,
                          element=True)
    return xph, th_out, n_h, spec


def _tap_reader(x_ref, stride: int, th_out: int, wo: int):
    """``tap(r, c)`` → the f32 ``(th_out, wo, ct)`` window of the loaded
    polyphase halo block that kernel tap ``(r, c)`` multiplies."""
    xv = x_ref[0].astype(jnp.float32)

    def tap(r, c):
        r0, c0 = r // stride, c // stride
        return xv[(r % stride) * stride + c % stride,
                  r0:r0 + th_out, c0:c0 + wo, :]
    return tap


# ---------------------------------------------------------------------------
# forward kernel (also computes dx in the backward via kernel reuse)
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, s_ref, b_ref, y_ref, *z_ref, stride, kh, kw,
                th_out, wo, act):
    """One (b, c-tile, h-tile) grid cell: k²-tap MAC + affine + act, all on
    the VPU with the accumulator VMEM-resident.  ``z_ref`` (the f32
    pre-affine output the backward consumes) exists only on the
    residual-saving call — the primal never allocates it."""
    ct = x_ref.shape[-1]
    tap = _tap_reader(x_ref, stride, th_out, wo)
    acc = jnp.zeros((th_out, wo, ct), jnp.float32)
    for r in range(kh):
        for s in range(kw):
            acc = acc + tap(r, s) * w_ref[r, s][None, None, :].astype(
                jnp.float32)
    if z_ref:
        z_ref[0][0] = acc
    u = acc * s_ref[0][None, None, :] + b_ref[0][None, None, :]
    y_ref[0] = _act_f32(act)(u).astype(y_ref.dtype)


def _dw_call(x, pads, w, scale, bias, *, stride, act, ho, wo, out_dtype,
             want_z, interpret):
    """Forward over ``x (B, H, W, C)`` under the conv padding ``pads``;
    returns ``y (B, Ho, Wo, C)`` and (when ``want_z``) the f32 pre-affine
    conv output for the backward."""
    b, c = x.shape[0], x.shape[-1]
    kh, kw = w.shape[0], w.shape[1]
    ct = _channel_tile(c)
    xph, th_out, n_h, x_spec = _halo_tiles(x, pads, kh, kw, stride, ho, wo)
    # tiling may overshoot Ho (last tile); the overshoot rows are sliced off
    ho_p = n_h * th_out

    grid = (b, c // ct, n_h)
    in_specs = [
        x_spec(lambda bi, ci, hi: bi, lambda bi, ci, hi: ci,
               lambda bi, ci, hi: hi),
        _vmem_spec((kh, kw, ct), lambda bi, ci, hi: (0, 0, ci)),
        _vmem_spec((1, ct), lambda bi, ci, hi: (0, ci)),
        _vmem_spec((1, ct), lambda bi, ci, hi: (0, ci)),
    ]
    out_spec = _vmem_spec((1, th_out, wo, ct),
                          lambda bi, ci, hi: (bi, hi, 0, ci))
    out_specs = [out_spec]
    out_shape = [_out_struct((b, ho_p, wo, c), out_dtype, x)]
    if want_z:
        # f32 pre-affine conv output, saved as the backward's residual —
        # only the residual-saving forward pays for this buffer
        out_specs.append(out_spec)
        out_shape.append(_out_struct((b, ho_p, wo, c), jnp.float32, x))
    kern = functools.partial(_fwd_kernel, stride=stride, kh=kh, kw=kw,
                             th_out=th_out, wo=wo, act=act)
    out = pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
    )(xph, w, scale, bias)
    if want_z:
        y, z = out
        return y[:, :ho], z[:, :ho]
    return out[0][:, :ho], None


# ---------------------------------------------------------------------------
# backward dw kernel: k²-tap correlation reduced over (B, H tiles)
# ---------------------------------------------------------------------------

def _dwgrad_kernel(x_ref, dz_ref, dw_ref, acc_ref, *, stride, kh, kw, th_out,
                   wo, ho):
    """One (c-tile, b, h-tile) grid cell accumulating ``dw[r·kw+s, c] +=
    Σ_{rows,cols} dz ⊙ x_shift(r,s)`` into VMEM scratch; written once at the
    last (b, h) step.  Operands arrive in their own dtype (bf16 on the hot
    path) and are widened here, once a block.  A column offset is a sublane
    shift, a row offset only picks other rows: each of the ``kw`` shifted
    slabs is made once and serves its ``kh`` taps."""
    bi = pl.program_id(1)
    hi = pl.program_id(2)
    nb = pl.num_programs(1)
    nh = pl.num_programs(2)

    @pl.when(jnp.logical_and(bi == 0, hi == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    dzv = dz_ref[0].astype(jnp.float32)
    if ho % th_out:
        # the last H tile overshoots dz: what lies past its end is not
        # zeros (whatever the buffer held), so it is masked, not multiplied
        row = lax.broadcasted_iota(jnp.int32, dzv.shape, 0)
        dzv = jnp.where(row < ho - hi * th_out, dzv, 0.0)
    xv = x_ref[0].astype(jnp.float32)
    for s in range(kw):
        c0 = s // stride
        slabs = [xv[p * stride + s % stride, :, c0:c0 + wo, :]
                 for p in range(stride)]
        for r in range(kh):
            r0 = r // stride
            prod = slabs[r % stride][r0:r0 + th_out] * dzv
            acc_ref[r * kw + s, :] += jnp.sum(jnp.sum(prod, axis=0), axis=0)

    @pl.when(jnp.logical_and(bi == nb - 1, hi == nh - 1))
    def _finalize():
        dw_ref[:] = acc_ref[:]


def _dwgrad_call(x, dz, pads, kh, kw, *, stride, ho, wo, interpret):
    """dw ``(kh, kw, C)`` float32 from the conv's input and the upstream
    conv-output gradient, each read from HBM once in its own dtype (the
    input through one pad, and for stride 2 the polyphase split, in XLA)."""
    b, c = x.shape[0], x.shape[-1]
    ct = _channel_tile(c)
    xph, th_out, n_h, x_spec = _halo_tiles(x, pads, kh, kw, stride, ho, wo)

    kern = functools.partial(_dwgrad_kernel, stride=stride, kh=kh, kw=kw,
                             th_out=th_out, wo=wo, ho=ho)
    dw = pl.pallas_call(
        kern,
        grid=(c // ct, b, n_h),
        in_specs=[
            x_spec(lambda ci, bi, hi: bi, lambda ci, bi, hi: ci,
                   lambda ci, bi, hi: hi),
            _vmem_spec((1, th_out, wo, ct),
                       lambda ci, bi, hi: (bi, hi, 0, ci)),
        ],
        out_specs=_vmem_spec((kh * kw, ct), lambda ci, bi, hi: (0, ci)),
        out_shape=_out_struct((kh * kw, c), jnp.float32, x),
        scratch_shapes=[_scratch((kh * kw, ct))],
        interpret=interpret,
    )(xph, dz)
    return dw.reshape(kh, kw, c)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

def fused_depthwise(x: jnp.ndarray, w: jnp.ndarray,
                    scale: Optional[jnp.ndarray] = None,
                    bias: Optional[jnp.ndarray] = None,
                    stride: Union[int, Tuple[int, int]] = 1,
                    padding: Union[str, int, None, Sequence] = "",
                    act: str = "silu",
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """``act(depthwise_conv(x, w) · scale + bias)`` in one VMEM pass.

    ``x`` is NHWC ``(B, H, W, C)``; ``w`` is ``(kh, kw, C)`` or the HWIO
    depthwise layout ``(kh, kw, 1, C)``; ``scale``/``bias`` are per-channel
    ``(C,)`` (None → identity affine).  ``padding`` takes the same values as
    :func:`ops.conv.resolve_padding` (``''`` = the reference's static
    symmetric torch padding, ``'same'`` = TF SAME, int, or an explicit
    ``[(lo, hi), (lo, hi)]``).  Equal H/W stride only (the EfficientNet
    families never use anisotropic depthwise strides).  Accumulation and the
    epilogue run in f32; the output is cast back to ``x.dtype``.

    Gradients flow through a custom VJP whose ``dx``/``dw`` are also Pallas
    (see module docstring).  ``interpret`` defaults to True off-TPU so the
    CPU suite runs the kernels under the Pallas interpreter.
    """
    assert x.ndim == 4, f"expected NHWC (B, H, W, C), got {x.shape}"
    if w.ndim == 4:  # HWIO depthwise (kh, kw, 1, C)
        assert w.shape[2] == 1, f"not a depthwise kernel: {w.shape}"
        w = w.reshape(w.shape[0], w.shape[1], w.shape[3])
    assert w.shape[-1] == x.shape[-1], (w.shape, x.shape)
    assert act in FUSED_DW_ACTS, f"act must be one of {FUSED_DW_ACTS}"
    sh, sw = _to_tuple(stride)
    assert sh == sw, f"anisotropic depthwise stride unsupported ({sh},{sw})"
    stride = int(sh)
    kh, kw = int(w.shape[0]), int(w.shape[1])
    interpret = resolve_interpret(interpret, "fused_depthwise")

    (ph0, ph1), (pw0, pw1) = _explicit_pads(
        resolve_padding(padding, (kh, kw), 1, stride), x.shape, (kh, kw),
        stride)

    b, h, wdim, c = x.shape
    hp, wp = h + ph0 + ph1, wdim + pw0 + pw1
    ho = (hp - kh) // stride + 1
    wo = (wp - kw) // stride + 1
    assert ho > 0 and wo > 0, (x.shape, pad, stride)

    out_dtype = x.dtype
    w32 = w.astype(jnp.float32)
    has_affine = scale is not None or bias is not None
    scale32 = (jnp.ones((c,), jnp.float32) if scale is None
               else scale.astype(jnp.float32))
    bias32 = (jnp.zeros((c,), jnp.float32) if bias is None
              else bias.astype(jnp.float32))
    # the backward reads the pre-affine conv output z only through the act
    # gradient and dscale — with an identity epilogue (exactly the training
    # call: stats are computed OUTSIDE the kernel) dz == dy and the affine
    # cotangents are gradients of internal constants, so saving z would
    # re-add the full-size f32 HBM write the fusion exists to remove
    needs_z = has_affine or act != "none"

    pads = ((ph0, ph1), (pw0, pw1))

    @jax.custom_vjp
    def _op(xv, wv, sv, bv):
        y, _ = _dw_call(xv, pads, wv, sv.reshape(1, c), bv.reshape(1, c),
                        stride=stride, act=act, ho=ho, wo=wo,
                        out_dtype=out_dtype, want_z=False,
                        interpret=interpret)
        return y

    def _op_fwd(xv, wv, sv, bv):
        y, z = _dw_call(xv, pads, wv, sv.reshape(1, c), bv.reshape(1, c),
                        stride=stride, act=act, ho=ho, wo=wo,
                        out_dtype=out_dtype, want_z=needs_z,
                        interpret=interpret)
        return y, (xv, wv, sv, bv, z)

    def _op_bwd(res, g):
        xv, wv, sv, bv, z = res
        g32 = g.astype(jnp.float32)
        if needs_z:
            u = z * sv[None, None, None, :] + bv[None, None, None, :]
            du = g32 * _act_grad_f32(act)(u) if act != "none" else g32
            # per-channel reductions fuse with the du pass in XLA
            dbias = jnp.sum(du, axis=(0, 1, 2))
            dscale = jnp.sum(du * z, axis=(0, 1, 2))
            dz = du * sv[None, None, None, :]
        else:
            # identity epilogue: dz == dy; the affine params are internal
            # constants, their cotangents are discarded upstream
            dz = g32
            dscale = jnp.zeros_like(sv)
            dbias = jnp.zeros_like(bv)
        # dx: transposed depthwise conv == the SAME forward kernel over the
        # interior-dilated dz padded by (k-1), with the kernel flipped
        dzd = lax.pad(dz, jnp.float32(0),
                      ((0, 0, 0),
                       (kh - 1, kh - 1, stride - 1),
                       (kw - 1, kw - 1, stride - 1),
                       (0, 0, 0)))
        wf = wv[::-1, ::-1].astype(jnp.float32)
        ones = jnp.ones((1, c), jnp.float32)
        zeros = jnp.zeros((1, c), jnp.float32)
        dxh = (ho - 1) * stride + kh      # rows of xp that received taps
        dxw = (wo - 1) * stride + kw
        dx_p, _ = _dw_call(dzd, ((0, 0), (0, 0)), wf, ones, zeros, stride=1,
                           act="none", ho=dxh, wo=dxw,
                           out_dtype=jnp.float32, want_z=False,
                           interpret=interpret)
        # rows/cols of the padded input beyond the last tap window got no
        # gradient; re-inflate to (Hp, Wp) then strip the conv padding
        dx_p = jnp.pad(dx_p, ((0, 0), (0, hp - dxh), (0, wp - dxw), (0, 0)))
        dx = dx_p[:, ph0:ph0 + h, pw0:pw0 + wdim]
        # identity epilogue: the kernel reads the upstream gradient as it
        # came (bf16 on the hot path), not a float32 copy of it
        dw = _dwgrad_call(xv, dz if needs_z else g, pads, kh, kw,
                          stride=stride, ho=ho, wo=wo, interpret=interpret)
        return (dx.astype(xv.dtype), dw.astype(wv.dtype),
                dscale.astype(sv.dtype), dbias.astype(bv.dtype))

    _op.defvjp(_op_fwd, _op_bwd)
    return _op(x, w32, scale32, bias32)


# ---------------------------------------------------------------------------
# the default path's depthwise stage: XLA's convolution, the kernel's dW
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("pads", "k", "stride",
                                             "interpret"))
def dw_filter_grad(x, g, *, pads, k, stride, interpret):
    """One trace and one lowering a distinct stage shape, however many
    blocks of the model share it."""
    return _dwgrad_call(x, g, pads, k, k, stride=stride, ho=g.shape[1],
                        wo=g.shape[2], interpret=interpret
                        ).reshape(k, k, 1, x.shape[-1])


def depthwise_conv(x: jnp.ndarray, kernel: jnp.ndarray, *, stride: int,
                   padding, dtype=None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """``flax.linen.Conv``'s depthwise convolution (``kernel`` HWIO
    ``(k, k, 1, C)``, ``padding`` what :func:`ops.conv.resolve_padding`
    gave) whose filter gradient is the reduction kernel.

    The forward and the input gradient are XLA's own convolution and its
    transpose, the same primitives on the same operands as ``nn.Conv``
    traces, so their values are that path's bit for bit.  ``dW`` reads
    ``x`` and the upstream gradient once, accumulates the k² taps in
    float32 and is rounded once, to the parameter's dtype."""
    k, c = int(kernel.shape[0]), int(x.shape[-1])
    assert kernel.shape == (k, k, 1, c), (kernel.shape, x.shape)
    if dtype is not None:
        x = x.astype(dtype)
    else:
        x = x.astype(jnp.promote_types(x.dtype, kernel.dtype))
    pads = _explicit_pads(padding, x.shape, (k, k), stride)
    interpret = resolve_interpret(interpret, "depthwise_conv filter gradient")

    def conv(xv, wv):
        # nn.Conv's call, argument for argument (flax/linen/linear.py)
        return lax.conv_general_dilated(
            xv, wv.astype(xv.dtype), (stride, stride), padding,
            lhs_dilation=(1, 1), rhs_dilation=(1, 1),
            dimension_numbers=lax.ConvDimensionNumbers(
                (0, 3, 1, 2), (3, 2, 0, 1), (0, 3, 1, 2)),
            feature_group_count=c, precision=None)

    @jax.custom_vjp
    def op(xv, wv):
        return conv(xv, wv)

    def op_fwd(xv, wv):
        return conv(xv, wv), (xv, wv)

    def op_bwd(res, g):
        xv, wv = res
        # linear in xv: the transpose is the one nn.Conv's gradient takes
        dx, = jax.vjp(lambda v: conv(v, wv), xv)[1](g)
        dw = dw_filter_grad(xv, g, pads=pads, k=k, stride=stride,
                            interpret=interpret)
        return dx, dw.astype(wv.dtype)

    op.defvjp(op_fwd, op_bwd)
    return op(x, kernel)
