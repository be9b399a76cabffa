"""Mixup.

Parity with ``/root/reference/dfd/timm/data/mixup.py``: ``one_hot``/
``mixup_target`` (:5-15), in-loop ``mixup_batch`` (:18-25), and the
collate-time ``FastCollateMixup`` (:27-51) that mixes the uint8 batch with its
reversed self under a single Beta-sampled ``lam`` and emits smoothed soft
targets.

The collate variant stays on host (numpy, uint8) and runs on the loader's one
producer thread, so what it costs a batch is the least the input period can
be; the in-loop variant is pure jnp so it can live inside the jitted train
step.

What the host blend costs at the flagship shape, ``(3, 600, 600, 12)`` uint8
(12.96 MB a batch), on the chip machine's host, by ``input_mixup_ms.train``:
as one whole-batch numpy expression 296.8 ms a batch (ledger, PR 24; 307.6 ms
in my chip run, PR 25) against a 151 ms device step — five fresh 51.8 MB
float32 temporaries, each page-faulted in, ~330 MB through DRAM for 13 M
output bytes.  Blended in cache-sized tiles (``_blend_tiled`` below), the same
arithmetic bit for bit, 36.9 ms beside the running train loop; timed alone on
that host, 318.6 ms before and 10.6 ms after (my chip runs, PR 25).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["one_hot_np", "mixup_target_np", "FastCollateMixup", "mixup_batch"]

# Elements of one float32 scratch tile of the host blend: 512 KB, so the two
# scratch tiles and the uint8 slices they read and write stay in a core's
# cache instead of streaming whole-batch temporaries through DRAM.
_TILE_ELEMS = 1 << 17


def one_hot_np(x: np.ndarray, num_classes: int, on_value: float = 1.0,
               off_value: float = 0.0) -> np.ndarray:
    out = np.full((len(x), num_classes), off_value, dtype=np.float32)
    out[np.arange(len(x)), x] = on_value
    return out


def mixup_target_np(target: np.ndarray, num_classes: int, lam: float = 1.0,
                    smoothing: float = 0.0) -> np.ndarray:
    """Soft targets: lam * y + (1-lam) * y[::-1], label-smoothed (:10-15)."""
    off = smoothing / num_classes
    on = 1.0 - smoothing + off
    y1 = one_hot_np(target, num_classes, on, off)
    y2 = one_hot_np(target[::-1], num_classes, on, off)
    return lam * y1 + (1.0 - lam) * y2


def _blend_tiled(images: np.ndarray, lam: float) -> np.ndarray:
    """``round(images * lam + images[::-1] * (1 - lam))``, a fresh uint8 array.

    Bit-identical to the whole-batch expression
    ``images.astype(f32) * lam + images[::-1].astype(f32) * (1.0 - lam)``,
    rounded half-even and cast to uint8: the per-element arithmetic is the
    same float32 cast, two float32 products, float32 sum and ``np.round``;
    only the order of the memory traffic changes.  The batch is walked in
    tiles of rows (axis 1); a tile holds every batch row's slice, so the
    reversed batch is a free view of it.  The tile length follows from the
    shape (about ``_TILE_ELEMS`` elements, at least one row); a batch smaller
    than one tile is one tile.  The two float32 scratch tiles live for the
    call only: both loader backends call from their own thread.  The input
    is only read (it may be a strided view of a shm slab that is recycled).
    """
    out = np.empty(images.shape, np.uint8)
    n_rows = images.shape[1]
    per_row = max(1, images[:, :1].size)
    rows = max(1, min(n_rows, _TILE_ELEMS // per_row))
    a = np.empty(images.shape[:1] + (rows,) + images.shape[2:], np.float32)
    b = np.empty_like(a)
    for r0 in range(0, n_rows, rows):
        src = images[:, r0:r0 + rows]           # the last tile may be short
        ta, tb = a[:, :src.shape[1]], b[:, :src.shape[1]]
        ta[...] = src
        ta *= lam
        tb[...] = src[::-1]
        tb *= 1.0 - lam
        ta += tb
        np.round(ta, out=ta)
        out[:, r0:r0 + rows] = ta
    return out


class FastCollateMixup:
    """Collate-time uint8 mixup (:27-51), with an explicit RNG.

    Call with the already-stacked uint8 batch ``(B, H, W, C)`` and int labels;
    returns the mixed uint8 batch and float32 soft targets.

    ``blend=False`` (set by the loader factory under ``--augment-device
    on``) elides the image blend only: lambda is still drawn from the
    identical stream and the soft targets still computed here, while the
    DeviceLoader re-derives the same lambda and blends inside its jitted
    prologue (``data/device_augment.py::device_mixup_blend``, bit-exact
    vs the host blend) — host cost drops to the target math.

    The blend runs in cache-sized tiles (``_blend_tiled``) and returns a
    fresh array; ``lam == 1.0`` and ``blend=False`` return the input
    itself.  The object keeps no per-call state.
    """

    def __init__(self, mixup_alpha: float = 1.0, label_smoothing: float = 0.1,
                 num_classes: int = 1000, blend: bool = True):
        self.mixup_alpha = mixup_alpha
        self.label_smoothing = label_smoothing
        self.num_classes = num_classes
        self.mixup_enabled = True
        self.blend = blend

    def __call__(self, images: np.ndarray, targets: np.ndarray,
                 rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
        lam = 1.0
        if self.mixup_enabled:
            lam = float(rng.beta(self.mixup_alpha, self.mixup_alpha))
        soft = mixup_target_np(targets, self.num_classes, lam,
                               self.label_smoothing)
        if lam == 1.0 or not self.blend:
            return images, soft
        return _blend_tiled(images, lam), soft


def mixup_batch(images: jnp.ndarray, targets: jnp.ndarray, rng: jax.Array,
                alpha: float = 0.2, num_classes: int = 1000,
                smoothing: float = 0.1, disable: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """In-loop device-side mixup (:18-25) — jit-safe.

    ``disable=True`` must be a Python (static) bool; everything else traces.
    """
    if disable:
        lam = jnp.float32(1.0)
    else:
        lam = jax.random.beta(rng, alpha, alpha)
    mixed = images * lam + jnp.flip(images, axis=0) * (1.0 - lam)
    off = smoothing / num_classes
    on = 1.0 - smoothing + off
    y1 = jax.nn.one_hot(targets, num_classes) * (on - off) + off
    y2 = jax.nn.one_hot(jnp.flip(targets, axis=0), num_classes) * (on - off) + off
    soft = lam * y1 + (1.0 - lam) * y2
    return mixed, soft
