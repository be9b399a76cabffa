"""Plain collate and uint8 mixup: what the host loader should hand the
device, rebuilt from the pool's rows.

The program's host loader stacks the rows of a batch and, under ``--mixup``,
blends each row with the row at the mirrored place, ``lam * a + (1 - lam) *
a[::-1]``, rounds to the nearest uint8 code and mixes the smoothed one-hot
targets alike (Zhang et al. 2018, as timm's collate does it).  Here the same
is computed in float64 from the pool, so the batches the reference trains on
are its own, and the program's batches are compared with them code by code.
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Sequence, Tuple

import numpy as np


def _digest(row: np.ndarray) -> bytes:
    return hashlib.blake2b(np.ascontiguousarray(row).tobytes(),
                           digest_size=16).digest()


def pool_index(pool: np.ndarray) -> Dict[bytes, int]:
    return {_digest(r): i for i, r in enumerate(pool)}


def find_rows(index: Dict[bytes, int], rows: np.ndarray) -> List[int]:
    """The pool index of each row of an unmixed batch, by content; -1 for a
    row that is no row of the pool."""
    return [index.get(_digest(r), -1) for r in rows]


def soft_targets(labels: Sequence[int], lam: float, smoothing: float,
                 num_classes: int) -> np.ndarray:
    off = smoothing / num_classes
    y = np.full((len(labels), num_classes), off, np.float64)
    y[np.arange(len(labels)), np.asarray(labels)] = 1.0 - smoothing + off
    return (lam * y + (1.0 - lam) * y[::-1]).astype(np.float32)


def rebuild_batch(pool: np.ndarray, labels: np.ndarray, idx: Sequence[int],
                  lam: float, smoothing: float, num_classes: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """(uint8 images, float32 soft targets) of one batch."""
    rows = pool[np.asarray(idx)]
    if lam != 1.0:
        mixed = lam * rows.astype(np.float64) \
            + (1.0 - lam) * rows[::-1].astype(np.float64)
        rows = np.clip(np.rint(mixed), 0, 255).astype(np.uint8)
    return rows, soft_targets(labels[np.asarray(idx)], lam, smoothing,
                              num_classes)
