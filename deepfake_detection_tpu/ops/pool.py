"""Pooling layers.

Replaces ``layers/adaptive_avgmax_pool.py`` (SelectAdaptivePool2d :70),
``layers/median_pool.py`` and ``layers/avg_pool2d_same.py``.  TF-"SAME"
average pooling is native XLA padding here — the reference's AvgPool2dSame
shim disappears.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


def adaptive_pool_feat_mult(pool_type: str = "avg") -> int:
    """Output-channel multiplier: 2 for catavgmax else 1 (adaptive_avgmax_pool.py:63)."""
    return 2 if pool_type == "catavgmax" else 1


def global_pool_nhwc(x, pool_type: str = "avg"):
    """Global spatial pool NHWC → NC (adaptive_avgmax_pool.py:25-60 semantics)."""
    if not pool_type:
        return x
    avg = jnp.mean(x, axis=(1, 2))
    if pool_type == "avg":
        return avg
    mx = jnp.max(x, axis=(1, 2))
    if pool_type == "max":
        return mx
    if pool_type == "avgmax":
        return 0.5 * (avg + mx)
    if pool_type == "catavgmax":
        return jnp.concatenate([avg, mx], axis=-1)
    raise ValueError(f"Invalid pool type: {pool_type!r}")


class SelectAdaptivePool2d(nn.Module):
    """Selectable global pooling head (adaptive_avgmax_pool.py:70-101)."""
    pool_type: str = "avg"
    flatten: bool = True

    def feat_mult(self) -> int:
        return adaptive_pool_feat_mult(self.pool_type)

    @nn.compact
    def __call__(self, x):
        out = global_pool_nhwc(x, self.pool_type)
        if not self.flatten and out.ndim == 2:
            out = out[:, None, None, :]
        return out


def max_pool2d_torch(x, window: Tuple[int, int], strides: Tuple[int, int],
                     padding: int = 0, ceil_mode: bool = False):
    """torch ``nn.MaxPool2d`` semantics on NHWC (static shapes under jit).

    Symmetric ``padding`` on both sides; ``ceil_mode`` adds end padding so
    a final partial window is kept — torch's rule that a window may not
    *start* in the right padded region is applied.  XLA 'SAME' equals this
    only at odd input sizes; at even input + stride 2 the window grids
    differ by one pixel (same class of parity break as resolve_padding's
    static-symmetric case — found by the trained-flagship conversion gate,
    round 5).  ``nn.max_pool`` pads with -inf, matching torch's
    clip-to-valid semantics for max.
    """
    if not ceil_mode:
        # floor mode: flax's floor output formula already drops partial
        # windows, so plain symmetric padding is torch-exact
        p = ((padding, padding),) * 2
        return nn.max_pool(x, window, strides=strides, padding=p)
    pads = []
    outs = []
    for dim, k, s in zip(x.shape[1:3], window, strides):
        # torch's ceil_mode output count: ceil formula, then drop the last
        # window if it would START in the right padded region
        out = -((dim + 2 * padding - k) // -s) + 1
        if (out - 1) * s >= dim + padding:
            out -= 1
        outs.append(out)
        # end pad so flax's floor formula keeps exactly torch's windows; a
        # NEGATIVE required pad (reachable when stride > kernel interacts
        # with the decrement rule) cannot be expressed as padding — clamp
        # to 0 and slice the surplus trailing window(s) off below instead
        # of silently emitting one extra window
        pads.append((padding, max(0, (out - 1) * s + k - dim - padding)))
    y = nn.max_pool(x, window, strides=strides, padding=pads)
    # both grids start windows at i*s - padding, so torch's output is
    # exactly the first outs[...] windows; a no-op slice in the common case
    return y[:, :outs[0], :outs[1], :]


def avg_pool2d_torch(x, window: Tuple[int, int], strides: Tuple[int, int],
                     padding: int = 0, count_include_pad: bool = True):
    """torch ``nn.AvgPool2d`` (floor mode) on NHWC: symmetric zero padding,
    pad zeros in the divisor when ``count_include_pad`` (torch's default).
    The res2net/dla downsample pools are ``AvgPool2d(3, stride, padding=1)``
    — at even input + stride 2 XLA 'SAME' shifts the window grid one pixel
    (the round-5 parity class)."""
    p = ((padding, padding),) * 2
    return nn.avg_pool(x, window, strides=strides, padding=p,
                       count_include_pad=count_include_pad)


def avg_pool2d_same(x, window: Tuple[int, int], strides: Tuple[int, int],
                    count_include_pad: bool = True):
    """TF-SAME average pool — XLA-native (replaces avg_pool2d_same.py:21)."""
    if count_include_pad:
        return nn.avg_pool(x, window, strides=strides, padding="SAME")
    ones = jnp.ones(x.shape[:-1] + (1,), x.dtype)
    summed = nn.avg_pool(x, window, strides=strides, padding="SAME")
    counts = nn.avg_pool(ones, window, strides=strides, padding="SAME")
    return summed / counts


def median_pool2d(x, kernel_size: int = 3, stride: int = 1,
                  padding: str = "SAME"):
    """Median filter (median_pool.py:8) via patch extraction + median.

    Patch extraction lowers to one strided conv-style gather; median is a sort
    over a small static axis — both XLA-friendly, no dynamic shapes.
    """
    B, H, W, C = x.shape
    k = kernel_size
    patches = jax.lax.conv_general_dilated_patches(
        jnp.moveaxis(x, -1, 1), (k, k), (stride, stride), padding,
    )  # (B, C*k*k, H', W')
    Ho, Wo = patches.shape[2], patches.shape[3]
    patches = patches.reshape(B, C, k * k, Ho, Wo)
    med = jnp.median(patches, axis=2)
    return jnp.moveaxis(med, 1, -1)


class MedianPool2d(nn.Module):
    kernel_size: int = 3
    stride: int = 1
    padding: str = "SAME"

    @nn.compact
    def __call__(self, x):
        return median_pool2d(x, self.kernel_size, self.stride, self.padding)
