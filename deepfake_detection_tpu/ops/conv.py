"""Convolution factory: plain / depthwise / mixed / conditional.

Replaces ``layers/create_conv2d.py`` (:11), ``layers/conv2d_same.py``,
``layers/mixed_conv2d.py`` (:20) and ``layers/cond_conv2d.py`` (:83-121).

TPU notes:
* Padding carries checkpoint-parity semantics (see :func:`resolve_padding`):
  pad_type ``''`` (non-tf families) is the reference's STATIC symmetric
  torch padding, expressed as an explicit XLA padding config; pad_type
  ``'same'`` (tf_* variants) is TF SAME, which XLA implements natively — so
  only the *dynamic* ``Conv2dSame`` shim vanishes, not the static/dynamic
  distinction itself.  Both forms lower to one conv, no separate pad op.
* CondConv's per-sample expert mixing is an einsum + a vmapped conv; XLA
  lowers the vmap to one batched/grouped convolution on the MXU — same trick
  as the reference's grouped-conv reshape, minus the manual reshapes.

Layout is NHWC, kernels HWIO (XLA/TPU native).
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def _to_tuple(v) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


def resolve_padding(padding: Union[str, int, None], kernel_size, dilation=1,
                    stride=1):
    """Map reference pad_type strings onto XLA padding specs.

    ``''`` (the non-tf families' default) → the reference's STATIC symmetric
    padding ``((s-1) + d*(k-1)) // 2`` per side (conv2d_same.py
    ``get_padding``).  This equals XLA 'SAME' at stride 1 (odd kernels) and
    at odd input sizes, but at even input + stride>1 torch pads both sides
    where SAME pads only the end — a one-pixel window-grid shift that
    breaks trained-checkpoint parity at the flagship's 600² (found by the
    trained-flagship conversion gate, round 5).

    ``'same'`` → XLA 'SAME' (true TF semantics — the tf_* variants' dynamic
    ``Conv2dSame`` shim is exactly this, natively).  ``'valid'`` → 'VALID';
    int → explicit symmetric.
    """
    if padding is None or padding == "":
        ks, dl, st = _to_tuple(kernel_size), _to_tuple(dilation), \
            _to_tuple(stride)
        return [(p, p) for p in
                (((s - 1) + d * (k - 1)) // 2 for k, d, s in zip(ks, dl, st))]
    if str(padding).lower() == "same":
        return "SAME"
    if str(padding).lower() == "valid":
        return "VALID"
    if isinstance(padding, int):
        return [(padding, padding), (padding, padding)]
    return padding


def conv_kernel_init_goog(key, shape, dtype=jnp.float32):
    """TF/EfficientNet conv init: N(0, sqrt(2/fan_out)), fan_out = kh*kw*out
    (efficientnet_builder.py:537-575)."""
    fan_out = shape[0] * shape[1] * shape[-1]
    return jax.random.normal(key, shape, dtype) * np.sqrt(2.0 / fan_out)


def dense_init_goog(key, shape, dtype=jnp.float32):
    """TF head init: U(-1/sqrt(out), 1/sqrt(out)) (efficientnet_builder.py:566-571)."""
    fan_out = shape[-1]
    init_range = 1.0 / np.sqrt(fan_out)
    return jax.random.uniform(key, shape, dtype, -init_range, init_range)


# ---------------------------------------------------------------------------
# who computes a depthwise stage's filter gradient (ops/depthwise_pallas.py
# has the kernel; the stage that asks is Conv2d below)
# ---------------------------------------------------------------------------

class _GradScope(threading.local):
    devices = 1         # a program nobody described runs on one device
    platform = None     # None: jax.default_backend()
    census = None


_grad_scope = _GradScope()


@contextlib.contextmanager
def dw_grad_scope(devices: int = 1, platform: Optional[str] = None,
                  census: Optional[dict] = None):
    """TRACE-time description of the program a depthwise stage is traced
    into (``ops/norm.py:local_stats_scope``'s idiom): how many devices
    compile it and, where the process's own backend is not the target (a
    described chip, a test), for which platform.  ``make_train_step``
    enters it with its mesh's size.  ``census``, a dict, collects the stages
    traced inside by the implementation each got (:func:`count_stage`)."""
    was = _grad_scope.devices, _grad_scope.platform, _grad_scope.census
    _grad_scope.devices = int(devices)
    # what an inner scope does not say, the enclosing one still does
    _grad_scope.platform = platform or was[1]
    _grad_scope.census = census if census is not None else was[2]
    try:
        yield
    finally:
        (_grad_scope.devices, _grad_scope.platform,
         _grad_scope.census) = was


#: from this batch on XLA's own filter gradient is one fusion (see
#: :func:`dw_grad_impl`)
_XLA_SOUND_BATCH = 8


def dw_grad_impl(x_shape, kernel_size: int, stride: int, dtype,
                 devices: Optional[int] = None,
                 platform: Optional[str] = None) -> str:
    """``'kernel'`` or ``'xla'``: who computes the filter gradient of one
    depthwise stage, from what the trace can observe: the static shapes,
    the operand dtype, the devices that compile the program and their
    platform (default: the enclosing :func:`dw_grad_scope`).

    At small batch XLA's TPU compiler rewrites the stage space-to-batch and
    feeds the filter gradient a k-fold copy of its input, built by whole
    passes over that copy; from batch 8 on it keeps the gradient one sound
    fusion.  The table behind the rule is PERF.md section 6 (PR 29's
    probe).  The kernel has no partitioning rule, so a program over several
    devices keeps XLA's gradient; off the TPU the kernel could only be
    interpreted, which is a test's business (``dw_grad_scope(platform=)``)
    and never a path."""
    devices = _grad_scope.devices if devices is None else devices
    platform = platform or _grad_scope.platform or jax.default_backend()
    if platform != "tpu" or devices > 1:
        return "xla"
    b = int(x_shape[0])
    if int(stride) not in (1, 2) or b >= _XLA_SOUND_BATCH:
        return "xla"
    return "kernel"


def count_stage(impl: str, x_shape, kernel_size: int, stride: int) -> None:
    """One depthwise stage traced with ``impl``, into the enclosing scope's
    census where it keeps one: ``{impl: [(x_shape, k, stride), ...]}``."""
    if _grad_scope.census is not None:
        _grad_scope.census.setdefault(impl, []).append(
            (tuple(int(d) for d in x_shape), int(kernel_size), int(stride)))


def dw_grad_census(model, x_shape, dtype, devices: int = 1,
                   platform: Optional[str] = None) -> dict:
    """The depthwise stages of one training trace of ``model`` over an
    ``x_shape`` batch, by implementation (:func:`count_stage`'s dict):
    fixed by the shapes, so one abstract trace finds it."""
    census: dict = {}
    key = jax.random.PRNGKey(0)
    with dw_grad_scope(devices, platform, census):
        jax.eval_shape(lambda: model.init(
            {"params": key, "dropout": key}, jnp.zeros(x_shape, dtype),
            training=True))
    return census


class _Kernel(nn.Module):
    """Declares ``kernel`` exactly like ``nn.Conv`` (same name, init, f32),
    for the paths that compute the convolution themselves."""
    shape: Tuple[int, ...]
    kernel_init: Callable = conv_kernel_init_goog

    @nn.compact
    def __call__(self):
        return self.param("kernel", self.kernel_init, self.shape)


def _kernel_grad_stage(conv: "Conv2d", x, ks, strides) -> bool:
    """Whether this call of ``conv`` is a depthwise stage that takes the
    kernel's filter gradient; counts the stage either way.  (A function,
    not a method: flax would record a method's result among the module's
    intermediates.)"""
    if not (conv.depthwise and x.ndim == 4 and not conv.use_bias
            and ks[0] == ks[1] and strides[0] == strides[1]
            and _to_tuple(conv.dilation) == (1, 1)
            and conv.groups == conv.out_chs == x.shape[-1]):
        return False
    dtype = conv.dtype or jnp.promote_types(x.dtype, jnp.float32)
    impl = dw_grad_impl(x.shape, ks[0], strides[0], dtype)
    count_stage(impl, x.shape, ks[0], strides[0])
    return impl == "kernel"


class Conv2d(nn.Module):
    """NHWC conv; depthwise via ``groups == in_chs`` like the reference factory.

    ``depthwise`` (set by :func:`create_conv2d` for a single square
    depthwise kernel) lets the stage ask :func:`dw_grad_impl` who computes
    its filter gradient.  Where the answer is
    XLA the stage is the plain ``nn.Conv`` call below with nothing around
    it; where it is the kernel, the same parameter (``conv/kernel``), the
    same forward and the same input gradient, and ``dW`` by one reduction
    (``depthwise_conv``)."""
    out_chs: int
    kernel_size: Union[int, Tuple[int, int]] = 3
    stride: Union[int, Tuple[int, int]] = 1
    dilation: Union[int, Tuple[int, int]] = 1
    groups: int = 1
    padding: Union[str, int, None] = ""
    use_bias: bool = False
    kernel_init: Callable = conv_kernel_init_goog
    dtype: Any = None
    depthwise: bool = False

    @nn.compact
    def __call__(self, x):
        ks = _to_tuple(self.kernel_size)
        strides = _to_tuple(self.stride)
        padding = resolve_padding(self.padding, ks, self.dilation,
                                  self.stride)
        if _kernel_grad_stage(self, x, ks, strides):
            from .depthwise_pallas import depthwise_conv
            kernel = _Kernel(ks + (1, self.out_chs), self.kernel_init,
                             name="conv")()
            return depthwise_conv(x, kernel, stride=strides[0],
                                  padding=padding, dtype=self.dtype)
        return nn.Conv(
            features=self.out_chs,
            kernel_size=ks,
            strides=strides,
            kernel_dilation=_to_tuple(self.dilation),
            feature_group_count=self.groups,
            padding=padding,
            use_bias=self.use_bias,
            kernel_init=self.kernel_init,
            dtype=self.dtype,
            name="conv",
        )(x)


class MixedConv2d(nn.Module):
    """Channel-split multi-kernel conv (MixNet; mixed_conv2d.py:20-50).

    Channels are split as equally as possible across kernel sizes (first split
    absorbs the remainder, matching the reference's np.array_split behavior).
    """
    out_chs: int
    kernel_size: Sequence[int] = (3, 5)
    stride: int = 1
    dilation: int = 1
    depthwise: bool = False
    padding: Union[str, int, None] = ""
    use_bias: bool = False
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        in_chs = x.shape[-1]
        n = len(self.kernel_size)
        in_splits = np.array_split(np.arange(in_chs), n)
        out_sizes = [len(a) for a in np.array_split(np.arange(self.out_chs), n)]
        outs = []
        start = 0
        for i, (ks, idx, out_c) in enumerate(zip(self.kernel_size, in_splits, out_sizes)):
            chunk = x[..., start:start + len(idx)]
            start += len(idx)
            # depthwise grouping derives from the INPUT split: groups must
            # equal the split's input channels (flax maps groups onto
            # feature_group_count, whose contract is per-input-channel).
            # Deriving it from out_c silently mis-grouped any depthwise
            # mixed conv whose split had in != out.
            if self.depthwise and len(idx) != out_c:
                raise ValueError(
                    f"MixedConv2d depthwise split {i}: input split has "
                    f"{len(idx)} channels but the output split has {out_c} "
                    f"— depthwise requires in == out per split "
                    f"(in_chs={in_chs}, out_chs={self.out_chs}, "
                    f"kernels={tuple(self.kernel_size)})")
            groups = len(idx) if self.depthwise else 1
            outs.append(Conv2d(out_c, ks, self.stride, self.dilation,
                               groups=groups, padding=self.padding,
                               use_bias=self.use_bias, dtype=self.dtype,
                               name=f"conv_{i}")(chunk))
        return jnp.concatenate(outs, axis=-1)


class CondConv2d(nn.Module):
    """Conditionally-parameterized conv (cond_conv2d.py:83-121).

    Holds ``num_experts`` kernels; ``__call__`` takes per-sample routing
    weights (B, E), mixes kernels with an einsum, then applies one conv per
    sample via vmap (XLA batches it onto the MXU).
    """
    out_chs: int
    kernel_size: Union[int, Tuple[int, int]] = 3
    stride: int = 1
    dilation: int = 1
    groups: int = 1
    num_experts: int = 4
    padding: Union[str, int, None] = ""
    use_bias: bool = False
    dtype: Any = None

    @nn.compact
    def __call__(self, x, routing_weights):
        kh, kw = _to_tuple(self.kernel_size)
        in_chs = x.shape[-1]
        kshape = (kh, kw, in_chs // self.groups, self.out_chs)

        def expert_init(key, shape, dtype=jnp.float32):
            # per-expert goog init on the underlying kernel shape
            # (cond_conv2d.py:20-31 get_condconv_initializer)
            keys = jax.random.split(key, shape[0])
            return jnp.stack([conv_kernel_init_goog(k, shape[1:], dtype)
                              for k in keys])

        weight = self.param("weight", expert_init,
                            (self.num_experts,) + kshape)
        # per-sample kernel: (B, kh, kw, cin/g, cout)
        mixed = jnp.einsum("be,ehwio->bhwio",
                           routing_weights.astype(weight.dtype), weight)
        pad = resolve_padding(self.padding, (kh, kw), self.dilation,
                              self.stride)
        dn = jax.lax.conv_dimension_numbers(
            (1,) + x.shape[1:], kshape, ("NHWC", "HWIO", "NHWC"))

        def one(xi, ki):
            return jax.lax.conv_general_dilated(
                xi[None], ki, window_strides=_to_tuple(self.stride),
                padding=pad, rhs_dilation=_to_tuple(self.dilation),
                dimension_numbers=dn, feature_group_count=self.groups)[0]

        y = jax.vmap(one)(x.astype(mixed.dtype), mixed)
        if self.use_bias:
            bias = self.param("bias", lambda k, s: jnp.zeros(s),
                              (self.num_experts, self.out_chs))
            y = y + jnp.einsum("be,eo->bo", routing_weights, bias)[:, None, None, :]
        return y


# ---------------------------------------------------------------------------
# Space-to-depth stem rewrite (MLPerf TPU-pod ResNet trick, Kumar et al. 2019)
# ---------------------------------------------------------------------------

def space_to_depth(x: jnp.ndarray, block: int = 2) -> jnp.ndarray:
    """NHWC pixel-shuffle: ``(B, H, W, C) → (B, H/b, W/b, b²·C)``.

    Channel layout is ``(di, dj, c)``-major — the layout
    :func:`space_to_depth_stem_kernel` assumes.  Pure reshape/transpose: XLA
    lowers it to a copy (loader prologue) or fuses it (in-model fallback).
    """
    b, h, w, c = x.shape
    assert h % block == 0 and w % block == 0, \
        f"space_to_depth needs H, W divisible by {block}, got {(h, w)}"
    x = x.reshape(b, h // block, block, w // block, block, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, h // block, w // block, block * block * c)


def depth_to_space(x, block: int = 2):
    """Inverse of :func:`space_to_depth` (same ``(di, dj, c)``-major channel
    layout); works on jax or numpy arrays."""
    b, h, w, c = x.shape
    assert c % (block * block) == 0, \
        f"depth_to_space needs C divisible by {block * block}, got {c}"
    x = x.reshape(b, h, w, block, block, c // (block * block))
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, h * block, w * block, c // (block * block))


def space_to_depth_stem_kernel(kernel: jnp.ndarray, pad_type: str = ""):
    """Rewrite a 3×3 stride-2 stem kernel for space-to-depth input.

    ``kernel`` is HWIO ``(3, 3, C, O)``; returns ``(k2, pad)`` where ``k2``
    is the ``(2, 2, 4C, O)`` stride-1 kernel over the s2d input and ``pad``
    the matching block-space padding config.  The rewrite embeds the 3×3
    taps into a zero 4×4 at the offset the original padding dictates (torch
    static-symmetric ``''`` pads 1 low → offset 1 + block-pad (1, 0); TF
    ``'same'`` at even input pads 1 high → offset 0 + block-pad (0, 1)), then
    regroups the 4×4 into 2×2 pixel blocks.  A pure, lossless, invertible
    scatter of the original weights: converted torch checkpoints keep their
    exact values, only the conv's window arithmetic changes (the conv output
    differs from the stride-2 original by float reassociation only — the
    taps and products are identical).
    """
    kh, kw, cin, cout = kernel.shape
    if (kh, kw) != (3, 3):
        raise ValueError(
            f"s2d stem rewrite covers the 3x3 stride-2 stem, got {(kh, kw)}")
    if str(pad_type).lower() == "same":
        off, pad = 0, (0, 1)
    elif pad_type in ("", None):
        off, pad = 1, (1, 0)
    else:
        raise ValueError(
            f"s2d stem supports pad_type ''|'same', got {pad_type!r}")
    k4 = jnp.zeros((4, 4, cin, cout), kernel.dtype)
    k4 = k4.at[off:off + 3, off:off + 3].set(kernel)
    k2 = k4.reshape(2, 2, 2, 2, cin, cout).transpose(0, 2, 1, 3, 4, 5)
    return k2.reshape(2, 2, 4 * cin, cout), [pad, pad]


def create_conv2d(out_chs: int, kernel_size, **kwargs) -> nn.Module:
    """Dispatch like the reference factory (create_conv2d.py:11-30):
    list kernel → MixedConv2d, num_experts>0 → CondConv2d, else Conv2d;
    depthwise=True maps to groups=out_chs."""
    if isinstance(kernel_size, (list, tuple)) and len(kernel_size) > 1:
        depthwise = kwargs.pop("depthwise", False)
        kwargs.pop("groups", None)
        return MixedConv2d(out_chs, kernel_size, depthwise=depthwise, **kwargs)
    if isinstance(kernel_size, (list, tuple)):
        kernel_size = kernel_size[0]
    if kwargs.get("depthwise", False):
        kwargs["groups"] = out_chs
    if kwargs.pop("num_experts", 0):
        raise ValueError("use CondConv2d directly; it needs routing weights")
    return Conv2d(out_chs, kernel_size, **kwargs)
