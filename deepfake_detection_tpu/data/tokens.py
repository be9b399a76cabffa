"""Token datasets: rows of integer ids for the sequence models.

A sample is one whole document of ``seq_len`` tokens: ``(ids, targets)``,
both int32 of that length, ``targets[t] = ids[t + 1]`` and -1 at the last
position (no target).  The datasets go through the same sampler,
``HostLoader`` producer and ``DeviceLoader`` staging as the image datasets
(``data/loader.py:create_token_loader``); the device prologue is the
identity.  ``sample_dtype`` tells the host loader what to stack.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

__all__ = ["SyntheticTokenDataset", "TokenFileDataset", "shift_targets",
           "zipf_ids"]


def shift_targets(ids: np.ndarray) -> np.ndarray:
    out = np.full(ids.shape, -1, np.int32)
    out[..., :-1] = ids[..., 1:]
    return out


def zipf_ids(rng: np.random.Generator, shape, vocab_rows: int,
             s: float = 1.0) -> np.ndarray:
    """ids below ``vocab_rows`` by a Zipf law: P(id = k) ~ 1 / (k + 1)^s."""
    p = 1.0 / np.arange(1, vocab_rows + 1, dtype=np.float64) ** s
    cdf = np.cumsum(p / p.sum())
    ids = np.searchsorted(cdf, rng.random(shape), side="right")
    return np.minimum(ids, vocab_rows - 1).astype(np.int32)


class _TokenRows:
    sample_dtype = np.int32

    def set_epoch(self, epoch: int) -> None:
        pass

    def set_transform(self, transform) -> None:
        pass                    # rows are fed as they are

    def __len__(self) -> int:
        return self.length


class SyntheticTokenDataset(_TokenRows):
    """Seeded Zipf-distributed documents (``--dataset synthetic-tokens``)."""

    def __init__(self, length: int, seq_len: int, vocab_rows: int,
                 seed: int = 0, zipf_s: float = 1.0):
        self.length, self.seq_len = length, seq_len
        self.vocab_rows, self.seed, self.zipf_s = vocab_rows, seed, zipf_s

    def __getitem__(self, index: int,
                    rng: Optional[np.random.Generator] = None):
        g = np.random.default_rng(np.random.SeedSequence([self.seed, index]))
        ids = zipf_ids(g, (self.seq_len,), self.vocab_rows, self.zipf_s)
        return ids, shift_targets(ids)


class TokenFileDataset(_TokenRows):
    """Rows of int32 ids from a file (``--dataset tokens --data FILE``): a
    ``.npy`` array or raw little-endian int32, read through a memory map as
    consecutive documents of ``seq_len`` ids (a ragged tail is dropped)."""

    def __init__(self, path: str, seq_len: int, vocab_rows: int):
        flat = np.load(path, mmap_mode="r") if path.endswith(".npy") \
            else np.memmap(path, dtype="<i4", mode="r")
        flat = flat.reshape(-1)
        self.length = flat.shape[0] // seq_len
        if self.length == 0:
            raise ValueError(f"{path}: fewer than {seq_len} ids")
        self.rows = flat[:self.length * seq_len].reshape(self.length, seq_len)
        self.seq_len, self.vocab_rows = seq_len, vocab_rows

    def __getitem__(self, index: int,
                    rng: Optional[np.random.Generator] = None):
        ids = np.asarray(self.rows[index], np.int32)
        if ids.min() < 0 or ids.max() >= self.vocab_rows:
            raise ValueError(f"row {index}: id outside [0, {self.vocab_rows})")
        return ids, shift_targets(ids)
