"""The benchmark's own data: a seeded in-memory pool of uint8 clips.

It stands in for the packed pre-decoded cache: samples are already decoded,
so the loader's transform, collate, transfer and device prologue are what the
window measures, not JPEG decode (which has a cell of its own to come) and
not numpy's random generator.  Clips are smooth random fields with a little
pixel noise, distinct row by row, so that what the model computes depends on
the row it is given: on white noise every row looks the same to a deep
network, and batch-norm over three rows then amplifies rounding.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np
from PIL import Image


def make_pool(seed: int, n: int, h: int, w: int, c: int, grid: int = 7):
    """(n, h, w, c) uint8 and n labels, the same for the same seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x706f6f6c]))
    frames = c // 3 if c % 3 == 0 else 1
    cpf = c // frames
    pool = np.empty((n, h, w, c), np.uint8)
    for i in range(n):
        for f in range(frames):
            coarse = rng.integers(0, 256, (grid, grid, cpf), dtype=np.uint8)
            img = Image.fromarray(coarse.squeeze() if cpf == 1 else coarse)
            pool[i, :, :, f * cpf:(f + 1) * cpf] = np.asarray(
                img.resize((w, h), Image.BICUBIC)).reshape(h, w, cpf)
    noise = rng.integers(-8, 9, pool.shape, dtype=np.int8)
    pool = np.clip(pool.astype(np.int16) + noise, 0, 255).astype(np.uint8)
    labels = rng.integers(0, 2, n).astype(np.int64)
    return pool, labels


class PoolDataset:
    """A dataset over the pool with the interface the program's loaders use:
    ``__getitem__(index, rng)``.  The pool holds clips as the host's
    augmentation chain would emit them, as the program's own
    ``SyntheticDataset`` does, so the transform the loader factory attaches
    is kept and not applied: the host's share is collate, mixup and
    transfer.  ``length`` may pass the pool's size; indices wrap."""

    def __init__(self, pool: np.ndarray, labels: np.ndarray, length: int):
        self.pool, self.labels, self.length = pool, labels, int(length)
        self.transform: Optional[Callable] = None
        self.epoch = 0

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def set_transform(self, transform: Callable) -> None:
        self.transform = transform

    def __len__(self) -> int:
        return self.length

    def __getitem__(self, index: int, rng=None):
        i = int(index) % len(self.pool)
        return self.pool[i], int(self.labels[i])
