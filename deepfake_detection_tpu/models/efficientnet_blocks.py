"""EfficientNet building blocks (Flax/NHWC).

TPU-native re-design of ``/root/reference/dfd/timm/models/efficientnet_blocks.py``:
``ConvBnAct`` (:113), ``DepthwiseSeparableConv`` (:136), ``InvertedResidual``
(MBConv, :260), ``CondConvResidual`` (:431), ``EdgeResidual`` (:484),
``SqueezeExcite`` (:93), channel rounding helpers (:55-69).

Every block is a single fused region under XLA: pw-expand → BN → Swish →
dw → BN → Swish → SE → pw-linear → BN → drop_path+residual compiles to a
handful of MXU convs with elementwise epilogues fused in — no reason for the
reference's module-per-op granularity to survive at runtime.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.activations import get_act_fn
from ..ops.conv import (CondConv2d, Conv2d, MixedConv2d, _Kernel,
                        create_conv2d, space_to_depth_stem_kernel)
from ..ops.drop import DropPath
from ..ops.norm import BatchNorm2d, GroupNorm, Identity


def make_divisible(v, divisor: int = 8, min_value: Optional[int] = None) -> int:
    min_value = min_value or divisor
    new_v = max(min_value, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


def round_channels(channels, multiplier: float = 1.0, divisor: int = 8,
                   channel_min: Optional[int] = None) -> int:
    """Scale + round channel count (efficientnet_blocks.py:64-69)."""
    if not multiplier:
        return channels
    return make_divisible(channels * multiplier, divisor, channel_min)


def _norm(norm_layer: str, momentum, eps, axis_name, dtype, name):
    if norm_layer.startswith("split"):
        # AdvProp split BN: 'split<k>' (reference convert_splitbn_model,
        # split_batchnorm.py:41-69 — here a norm_layer option, since flax
        # modules cannot be surgically rewritten post-construction)
        from ..ops.norm import SplitBatchNorm2d
        return SplitBatchNorm2d(num_splits=int(norm_layer[5:] or 2),
                                momentum=momentum, eps=eps,
                                axis_name=axis_name, dtype=dtype, name=name)
    if norm_layer == "none":
        return Identity(name=name)
    if norm_layer == "gn":
        return GroupNorm(eps=eps, dtype=dtype, name=name)
    return BatchNorm2d(momentum=momentum, eps=eps, axis_name=axis_name,
                       dtype=dtype, name=name)


# ---------------------------------------------------------------------------
# Fused depthwise path (ops/depthwise_pallas.py) + space-to-depth stem.
#
# Both are pure EXECUTION rewrites: the parameter tree (names, shapes, inits,
# dtypes) is identical to the default path's, so one checkpoint serves both
# and the flags can flip between runs.  That is achieved by tiny modules that
# declare the same nested params the stock Conv2d / BatchNorm2d modules
# would, while the compute happens outside them.
# ---------------------------------------------------------------------------

class _DwConvParams(nn.Module):
    """Param mirror of ``Conv2d(name='conv_dw')``: path conv_dw/conv/kernel
    with the HWIO depthwise shape ``(kh, kw, 1, C)``."""
    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self):
        return _Kernel(self.shape, name="conv")()


class _BNInner(nn.Module):
    """Param mirror of ``nn.BatchNorm``: scale/bias params + mean/var
    batch_stats, same names, shapes, inits and dtypes."""
    features: int

    @nn.compact
    def __call__(self):
        f = (self.features,)
        scale = self.param("scale", nn.initializers.ones, f, jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, f, jnp.float32)
        mean = self.variable("batch_stats", "mean",
                             lambda s: jnp.zeros(s, jnp.float32), f)
        var = self.variable("batch_stats", "var",
                            lambda s: jnp.ones(s, jnp.float32), f)
        return scale, bias, mean, var


class _BNParams(nn.Module):
    """Param mirror of ``BatchNorm2d(name=<bn_name>)``: path <bn_name>/bn/*."""
    features: int

    @nn.compact
    def __call__(self):
        return _BNInner(self.features, name="bn")()


def fused_dw_eligible(dw_kernel_size, dilation: int, stride,
                      norm_layer: str) -> bool:
    """Whether a block's dw stage can route through the Pallas fused op:
    single square kernel (no MixedConv arms), no dilation, stride 1/2, plain
    BN (split/group/none norms keep the default path)."""
    return (isinstance(dw_kernel_size, int) and int(dilation) == 1
            and int(stride) in (1, 2) and norm_layer == "bn")


def _fused_dw_bn_act(block: nn.Module, x, training: bool, *, chs: int,
                     kernel_size: int, stride: int, pad_type, act,
                     bn_name: str, momentum: float, eps: float,
                     axis_name, dtype):
    """dw-conv → BN → act through the fused Pallas op, called from inside a
    block's ``@nn.compact`` __call__ (children splice in at block level).

    Eval folds the running stats into the kernel's per-channel affine
    epilogue — the whole stage is one VMEM-resident pass.  Training needs
    the batch statistics of the conv output before it can normalize, so the
    Pallas pass produces the conv output and the stats/normalize/act
    epilogue runs as one fused XLA elementwise pass, mirroring
    ``flax.linen.BatchNorm`` semantics exactly (f32 stats via E[x²]−E[x]²,
    clamped at 0; flax-convention momentum; optional ``axis_name`` pmean for
    cross-replica sync BN).  Gradients flow through the op's custom VJP.
    """
    from ..ops.depthwise_pallas import FUSED_DW_ACTS, fused_depthwise
    k = int(kernel_size)
    kernel = _DwConvParams((k, k, 1, chs), name="conv_dw")()
    scale, bias, ra_mean, ra_var = _BNParams(chs, name=bn_name)()
    act_name = "silu" if act in ("silu", "swish") else act
    kern_act = act_name if act_name in FUSED_DW_ACTS else "none"
    act_fn = get_act_fn(act)
    out_dtype = dtype if dtype is not None else \
        jnp.promote_types(x.dtype, jnp.float32)
    if dtype is not None:
        x = x.astype(dtype)

    if not training:
        inv = jax.lax.rsqrt(ra_var.value + eps)
        eff_scale = scale.astype(jnp.float32) * inv
        eff_bias = bias.astype(jnp.float32) - ra_mean.value * eff_scale
        y = fused_depthwise(x, kernel, eff_scale, eff_bias, stride=stride,
                            padding=pad_type, act=kern_act)
        y = y.astype(out_dtype)
        return y if kern_act == act_name else act_fn(y)

    z = fused_depthwise(x, kernel, None, None, stride=stride,
                        padding=pad_type, act="none")
    zf = z.astype(jnp.promote_types(z.dtype, jnp.float32))
    from ..ops.norm import (_active_local_stats, grouped_local_stats,
                            grouped_running_update)
    scope = _active_local_stats()
    if axis_name is None and scope is not None and scope.groups > 1:
        # unified GSPMD local-BN (ISSUE 12): per-group statistics via the
        # SAME ops/norm.py core as _LocalStatsBatchNorm — each mesh slot
        # normalizes with its own shard's stats, running stats take the
        # group mean (== the shard_map era's per-device update + pmean)
        zg, mu_g, var_g = grouped_local_stats(zf, scope.groups,
                                              scope.sharding)
        if not block.is_initializing():
            m = 1.0 - momentum      # flax convention (BatchNorm2d:70)
            ra_mean.value = grouped_running_update(ra_mean.value, mu_g, m)
            ra_var.value = grouped_running_update(ra_var.value, var_g, m)
        mul = jax.lax.rsqrt(var_g + eps)[:, None, None, None] \
            * scale.astype(jnp.float32)
        y = ((zg - mu_g[:, None, None, None]) * mul
             + bias.astype(jnp.float32))
        if scope.sharding is not None:
            y = jax.lax.with_sharding_constraint(y, scope.sharding)
        return act_fn(y.reshape(zf.shape).astype(out_dtype))
    mu = jnp.mean(zf, axis=(0, 1, 2))
    mu2 = jnp.mean(zf * zf, axis=(0, 1, 2))
    if axis_name is not None:
        mu, mu2 = jax.lax.pmean((mu, mu2), axis_name)
    var = jnp.maximum(0.0, mu2 - mu * mu)
    if not block.is_initializing():
        m = 1.0 - momentum          # flax convention (BatchNorm2d:70)
        ra_mean.value = m * ra_mean.value + (1.0 - m) * mu
        ra_var.value = m * ra_var.value + (1.0 - m) * var
    mul = jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    y = ((zf - mu[None, None, None]) * mul[None, None, None]
         + bias.astype(jnp.float32)[None, None, None])
    return act_fn(y.astype(out_dtype))


class SqueezeExcite(nn.Module):
    """EfficientNet-style SE (efficientnet_blocks.py:93-110): reduction is
    computed from ``reduced_base_chs`` (the block *input* chs), not the
    expanded chs."""
    se_ratio: float = 0.25
    reduced_base_chs: Optional[int] = None
    act: Any = "relu"
    gate_fn: Any = "sigmoid"
    divisor: int = 1
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        chs = x.shape[-1]
        base = self.reduced_base_chs or chs
        reduced_chs = make_divisible(base * self.se_ratio, self.divisor)
        s = jnp.mean(x, axis=(1, 2), keepdims=True)
        s = Conv2d(reduced_chs, 1, use_bias=True, dtype=self.dtype,
                   name="conv_reduce")(s)
        s = get_act_fn(self.act)(s)
        s = Conv2d(chs, 1, use_bias=True, dtype=self.dtype,
                   name="conv_expand")(s)
        return x * get_act_fn(self.gate_fn)(s)


class ConvBnAct(nn.Module):
    """conv → norm → act (efficientnet_blocks.py:113-133 / layers/conv_bn_act.py:10)."""
    out_chs: int
    kernel_size: Union[int, Sequence[int]] = 3
    stride: int = 1
    dilation: int = 1
    pad_type: str = ""
    act: Any = "relu"
    norm_layer: str = "bn"
    bn_momentum: float = 0.1
    bn_eps: float = 1e-5
    bn_axis_name: Optional[str] = None
    dtype: Any = None

    @nn.compact
    def __call__(self, x, training: bool = False):
        x = create_conv2d(self.out_chs, self.kernel_size, stride=self.stride,
                          dilation=self.dilation, padding=self.pad_type,
                          dtype=self.dtype, name="conv")(x)
        x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                  self.bn_axis_name, self.dtype, "bn1")(x, training=training)
        return get_act_fn(self.act)(x)


class _S2dStemConv(nn.Module):
    """Param mirror of ``Conv2d(name='conv')`` computing the space-to-depth
    stem: the parameter KEEPS the original ``(3, 3, C, stem)`` stride-2
    shape (checkpoints stay bit-compatible, converted torch weights load
    unchanged) and is re-scattered on the fly into the ``(2, 2, 4C, stem)``
    stride-1 kernel over the pixel-shuffled input.  The reshape is traced
    into the jit and is a tiny gather next to the conv itself."""
    out_chs: int
    pad_type: str = ""
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        in_chans = x.shape[-1] // 4
        kernel = _Kernel((3, 3, in_chans, self.out_chs), name="conv")()
        k2, pad = space_to_depth_stem_kernel(kernel, self.pad_type)
        if self.dtype is not None:
            x, k2 = x.astype(self.dtype), k2.astype(self.dtype)
        return jax.lax.conv_general_dilated(
            x, k2, window_strides=(1, 1), padding=pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


class ConvBnActS2d(nn.Module):
    """Drop-in stem replacement for ``ConvBnAct(stem, 3, stride=2)`` over
    space-to-depth input ``(B, H/2, W/2, 4C)``: a stride-1 2×2 conv whose
    contraction depth (4C·4 taps) tiles the MXU where the original
    12-channel 600² stem ran the systolic array at ~1/3 occupancy.  Same
    parameter tree as ConvBnAct (conv/conv/kernel + bn1)."""
    out_chs: int
    pad_type: str = ""
    act: Any = "relu"
    norm_layer: str = "bn"
    bn_momentum: float = 0.1
    bn_eps: float = 1e-5
    bn_axis_name: Optional[str] = None
    dtype: Any = None

    @nn.compact
    def __call__(self, x, training: bool = False):
        x = _S2dStemConv(self.out_chs, self.pad_type, dtype=self.dtype,
                         name="conv")(x)
        x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                  self.bn_axis_name, self.dtype, "bn1")(x, training=training)
        return get_act_fn(self.act)(x)


class DepthwiseSeparableConv(nn.Module):
    """dw conv → SE → pw conv; used where the MBConv expansion is 1
    (efficientnet_blocks.py:136-194)."""
    out_chs: int
    dw_kernel_size: Union[int, Sequence[int]] = 3
    stride: int = 1
    dilation: int = 1
    pad_type: str = ""
    act: Any = "relu"
    noskip: bool = False
    pw_kernel_size: int = 1
    pw_act: bool = False
    se_ratio: float = 0.0
    se_gate_fn: Any = "sigmoid"
    se_kwargs: Any = None    # {'act','gate_fn','reduce_mid','divisor'} overrides
    drop_path_rate: float = 0.0
    norm_layer: str = "bn"
    bn_momentum: float = 0.1
    bn_eps: float = 1e-5
    bn_axis_name: Optional[str] = None
    # 'off' | 'pallas' — route dw → BN → act through the fused VMEM-resident
    # kernel (ops/depthwise_pallas.py); parameter tree is identical either way
    fused_depthwise: str = "off"
    dtype: Any = None

    @nn.compact
    def __call__(self, x, training: bool = False):
        in_chs = x.shape[-1]
        has_residual = (self.stride == 1 and in_chs == self.out_chs
                        and not self.noskip)
        act = get_act_fn(self.act)
        shortcut = x
        if self.fused_depthwise == "pallas" and fused_dw_eligible(
                self.dw_kernel_size, self.dilation, self.stride,
                self.norm_layer):
            x = _fused_dw_bn_act(
                self, x, training, chs=in_chs,
                kernel_size=self.dw_kernel_size, stride=self.stride,
                pad_type=self.pad_type, act=self.act, bn_name="bn1",
                momentum=self.bn_momentum, eps=self.bn_eps,
                axis_name=self.bn_axis_name, dtype=self.dtype)
        else:
            x = create_conv2d(in_chs, self.dw_kernel_size,
                              stride=self.stride, dilation=self.dilation,
                              padding=self.pad_type, depthwise=True,
                              dtype=self.dtype, name="conv_dw")(x)
            x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                      self.bn_axis_name, self.dtype,
                      "bn1")(x, training=training)
            x = act(x)
        if self.se_ratio > 0.0:
            sek = dict(self.se_kwargs or {})
            sek.pop("reduce_mid", None)   # dw block: mid == in chs
            x = SqueezeExcite(self.se_ratio, reduced_base_chs=in_chs,
                              act=sek.pop("act", self.act),
                              gate_fn=sek.pop("gate_fn", self.se_gate_fn),
                              divisor=sek.pop("divisor", 1),
                              dtype=self.dtype, name="se")(x)
        x = create_conv2d(self.out_chs, self.pw_kernel_size,
                          padding=self.pad_type, dtype=self.dtype,
                          name="conv_pw")(x)
        x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                  self.bn_axis_name, self.dtype, "bn2")(x, training=training)
        if self.pw_act:
            x = act(x)
        if has_residual:
            x = DropPath(self.drop_path_rate, name="drop_path")(x, training=training)
            x = x + shortcut
        return x


class InvertedResidual(nn.Module):
    """MBConv (efficientnet_blocks.py:260-348)."""
    out_chs: int
    dw_kernel_size: Union[int, Sequence[int]] = 3
    stride: int = 1
    dilation: int = 1
    pad_type: str = ""
    act: Any = "relu"
    noskip: bool = False
    exp_ratio: float = 1.0
    exp_kernel_size: int = 1
    pw_kernel_size: int = 1
    se_ratio: float = 0.0
    se_gate_fn: Any = "sigmoid"
    se_kwargs: Any = None    # {'act','gate_fn','reduce_mid','divisor'} overrides
    drop_path_rate: float = 0.0
    norm_layer: str = "bn"
    bn_momentum: float = 0.1
    bn_eps: float = 1e-5
    bn_axis_name: Optional[str] = None
    # 'off' | 'pallas' — route dw → BN → act through the fused VMEM-resident
    # kernel (ops/depthwise_pallas.py); parameter tree is identical either way
    fused_depthwise: str = "off"
    dtype: Any = None

    def _mid_chs(self, in_chs: int) -> int:
        return make_divisible(in_chs * self.exp_ratio)

    @nn.compact
    def __call__(self, x, training: bool = False):
        in_chs = x.shape[-1]
        mid_chs = self._mid_chs(in_chs)
        has_residual = (in_chs == self.out_chs and self.stride == 1
                        and not self.noskip)
        act = get_act_fn(self.act)
        shortcut = x
        # point-wise expansion
        x = create_conv2d(mid_chs, self.exp_kernel_size, padding=self.pad_type,
                          dtype=self.dtype, name="conv_pw")(x)
        x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                  self.bn_axis_name, self.dtype, "bn1")(x, training=training)
        x = act(x)
        # depth-wise
        if self.fused_depthwise == "pallas" and fused_dw_eligible(
                self.dw_kernel_size, self.dilation, self.stride,
                self.norm_layer):
            x = _fused_dw_bn_act(
                self, x, training, chs=mid_chs,
                kernel_size=self.dw_kernel_size, stride=self.stride,
                pad_type=self.pad_type, act=self.act, bn_name="bn2",
                momentum=self.bn_momentum, eps=self.bn_eps,
                axis_name=self.bn_axis_name, dtype=self.dtype)
        else:
            x = create_conv2d(mid_chs, self.dw_kernel_size,
                              stride=self.stride, dilation=self.dilation,
                              padding=self.pad_type, depthwise=True,
                              dtype=self.dtype, name="conv_dw")(x)
            x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                      self.bn_axis_name, self.dtype,
                      "bn2")(x, training=training)
            x = act(x)
        if self.se_ratio > 0.0:
            sek = dict(self.se_kwargs or {})
            base = mid_chs if sek.pop("reduce_mid", False) else in_chs
            x = SqueezeExcite(self.se_ratio, reduced_base_chs=base,
                              act=sek.pop("act", self.act),
                              gate_fn=sek.pop("gate_fn", self.se_gate_fn),
                              divisor=sek.pop("divisor", 1),
                              dtype=self.dtype, name="se")(x)
        # point-wise linear projection
        x = create_conv2d(self.out_chs, self.pw_kernel_size,
                          padding=self.pad_type, dtype=self.dtype,
                          name="conv_pwl")(x)
        x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                  self.bn_axis_name, self.dtype, "bn3")(x, training=training)
        if has_residual:
            x = DropPath(self.drop_path_rate, name="drop_path")(x, training=training)
            x = x + shortcut
        return x


class CondConvResidual(nn.Module):
    """MBConv with conditionally-parameterized convs (efficientnet_blocks.py:431-481):
    routing = sigmoid(Linear(global_avg_pool(x))) shared by all three convs."""
    out_chs: int
    num_experts: int = 4
    dw_kernel_size: int = 3
    stride: int = 1
    dilation: int = 1
    pad_type: str = ""
    act: Any = "relu"
    noskip: bool = False
    exp_ratio: float = 1.0
    exp_kernel_size: int = 1
    pw_kernel_size: int = 1
    se_ratio: float = 0.0
    se_gate_fn: Any = "sigmoid"
    se_kwargs: Any = None    # {'act','gate_fn','reduce_mid','divisor'} overrides
    drop_path_rate: float = 0.0
    norm_layer: str = "bn"
    bn_momentum: float = 0.1
    bn_eps: float = 1e-5
    bn_axis_name: Optional[str] = None
    dtype: Any = None

    @nn.compact
    def __call__(self, x, training: bool = False):
        in_chs = x.shape[-1]
        mid_chs = make_divisible(in_chs * self.exp_ratio)
        has_residual = (in_chs == self.out_chs and self.stride == 1
                        and not self.noskip)
        act = get_act_fn(self.act)
        shortcut = x
        pooled = jnp.mean(x, axis=(1, 2))
        routing = jax.nn.sigmoid(
            nn.Dense(self.num_experts, dtype=self.dtype,
                     name="routing_fn")(pooled))
        x = CondConv2d(mid_chs, self.exp_kernel_size,
                       num_experts=self.num_experts, padding=self.pad_type,
                       dtype=self.dtype, name="conv_pw")(x, routing)
        x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                  self.bn_axis_name, self.dtype, "bn1")(x, training=training)
        x = act(x)
        x = CondConv2d(mid_chs, self.dw_kernel_size, stride=self.stride,
                       dilation=self.dilation, groups=mid_chs,
                       num_experts=self.num_experts, padding=self.pad_type,
                       dtype=self.dtype, name="conv_dw")(x, routing)
        x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                  self.bn_axis_name, self.dtype, "bn2")(x, training=training)
        x = act(x)
        if self.se_ratio > 0.0:
            x = SqueezeExcite(self.se_ratio, reduced_base_chs=in_chs,
                              act=self.act, gate_fn=self.se_gate_fn,
                              dtype=self.dtype, name="se")(x)
        x = CondConv2d(self.out_chs, self.pw_kernel_size,
                       num_experts=self.num_experts, padding=self.pad_type,
                       dtype=self.dtype, name="conv_pwl")(x, routing)
        x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                  self.bn_axis_name, self.dtype, "bn3")(x, training=training)
        if has_residual:
            x = DropPath(self.drop_path_rate, name="drop_path")(x, training=training)
            x = x + shortcut
        return x


class EdgeResidual(nn.Module):
    """EdgeTPU FusedMBConv: full kxk expansion conv instead of pw+dw
    (efficientnet_blocks.py:484-549)."""
    out_chs: int
    exp_kernel_size: int = 3
    stride: int = 1
    dilation: int = 1
    pad_type: str = ""
    act: Any = "relu"
    noskip: bool = False
    exp_ratio: float = 1.0
    fake_in_chs: int = 0
    pw_kernel_size: int = 1
    se_ratio: float = 0.0
    se_gate_fn: Any = "sigmoid"
    se_kwargs: Any = None    # {'act','gate_fn','reduce_mid','divisor'} overrides
    drop_path_rate: float = 0.0
    norm_layer: str = "bn"
    bn_momentum: float = 0.1
    bn_eps: float = 1e-5
    bn_axis_name: Optional[str] = None
    dtype: Any = None

    @nn.compact
    def __call__(self, x, training: bool = False):
        in_chs = x.shape[-1]
        base = self.fake_in_chs if self.fake_in_chs > 0 else in_chs
        mid_chs = make_divisible(base * self.exp_ratio)
        has_residual = (in_chs == self.out_chs and self.stride == 1
                        and not self.noskip)
        act = get_act_fn(self.act)
        shortcut = x
        x = create_conv2d(mid_chs, self.exp_kernel_size, stride=self.stride,
                          dilation=self.dilation, padding=self.pad_type,
                          dtype=self.dtype, name="conv_exp")(x)
        x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                  self.bn_axis_name, self.dtype, "bn1")(x, training=training)
        x = act(x)
        if self.se_ratio > 0.0:
            x = SqueezeExcite(self.se_ratio, reduced_base_chs=in_chs,
                              act=self.act, gate_fn=self.se_gate_fn,
                              dtype=self.dtype, name="se")(x)
        x = create_conv2d(self.out_chs, self.pw_kernel_size,
                          padding=self.pad_type, dtype=self.dtype,
                          name="conv_pwl")(x)
        x = _norm(self.norm_layer, self.bn_momentum, self.bn_eps,
                  self.bn_axis_name, self.dtype, "bn2")(x, training=training)
        if has_residual:
            x = DropPath(self.drop_path_rate, name="drop_path")(x, training=training)
            x = x + shortcut
        return x
