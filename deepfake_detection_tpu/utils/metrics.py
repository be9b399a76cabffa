"""Metrics.

Parity with ``/root/reference/dfd/timm/utils.py``: ``AverageMeter`` (:152),
``accuracy`` top-k percentage (:170-186).  ``accuracy`` is pure jnp so it runs
*inside* the jitted train/eval step; the reference instead pulled logits to
Python each step.  Cross-replica averaging is a ``lax.pmean`` at the call
site, replacing ``reduce_tensor`` (:256-260).
"""

from __future__ import annotations

import threading
from typing import List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp

__all__ = ["AverageMeter", "LatencyHistogram", "accuracy", "auc",
           "masked_mean"]


class AverageMeter:
    """Running average (reference :152-167)."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.val = 0.0
        self.avg = 0.0
        self.sum = 0.0
        self.count = 0.0

    def update(self, val: float, n: float = 1) -> None:
        self.val = val
        self.sum += val * n
        self.count += n
        self.avg = self.sum / self.count


#: moved to utils/prometheus.py (the jax-free observability floor the
#: fleet router shares); re-exported here for existing callers
from .prometheus import LatencyHistogram  # noqa: E402,F401


def accuracy(output: jnp.ndarray, target: jnp.ndarray,
             topk: Sequence[int] = (1,),
             weight: Optional[jnp.ndarray] = None
             ) -> Union[jnp.ndarray, list]:
    """Top-k precision in percent (reference :170-186).

    Soft targets (same shape as output) collapse to their argmax, matching the
    reference's mixup path (:177-178).  ``weight`` masks padded eval samples
    (the reference's duplicated-sample error doesn't exist here).
    """
    maxk = max(topk)
    if target.shape == output.shape:
        target = jnp.argmax(target, axis=-1)
    # top-k indices, descending
    pred = jnp.argsort(-output, axis=-1)[:, :maxk]            # (B, maxk)
    correct = pred == target[:, None]                          # (B, maxk)
    if weight is None:
        denom = target.shape[0]
        w = 1.0
    else:
        w = weight[:, None].astype(jnp.float32)
        denom = jnp.maximum(weight.sum(), 1)
    accs = [(correct[:, :k] * w).sum() * 100.0 / denom for k in topk]
    return accs[0] if len(topk) == 1 else accs


def auc(scores: jnp.ndarray, labels: jnp.ndarray,
        weight: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """ROC AUC via the rank statistic (Mann–Whitney U).

    The reference never computes AUC in code, but its released checkpoint is
    evaluated by AUC (README.md:35-40) and the north-star quality gate is
    "AUC ≥ the released GPU checkpoint" — so the framework
    ships the metric.  Pure jnp, O(n log n), static-shaped (ties get the
    usual midrank treatment), so it can run inside a jitted eval epoch.

    ``weight`` is a {0, 1} VALIDITY MASK (padded samples from the ordered
    sharded eval sampler), not a general sample weight: midranks are
    computed unweighted, so fractional weights would silently produce a
    wrong AUC.  Anything > 0 is treated as valid.

    ``scores``: higher ⇒ more positive; ``labels``: {0, 1}.
    """
    scores = scores.astype(jnp.float32).reshape(-1)
    labels = labels.reshape(-1)
    w = (jnp.ones_like(scores) if weight is None
         else (weight.reshape(-1) > 0).astype(jnp.float32))
    # midranks of the scores, computed without dynamic shapes: for each
    # element, rank = (#strictly-smaller) + (#equal + 1) / 2, with masked
    # entries pushed out of the comparison by ±inf on either side
    s = jnp.where(w > 0, scores, jnp.inf)
    order = jnp.argsort(s)
    sorted_s = s[order]
    n = scores.shape[0]
    first = jnp.searchsorted(sorted_s, sorted_s, side="left")
    last = jnp.searchsorted(sorted_s, sorted_s, side="right")
    midrank_sorted = (first + last + 1) / 2.0          # 1-based midranks
    ranks = jnp.zeros(n).at[order].set(midrank_sorted)
    pos = (labels > 0).astype(jnp.float32) * w
    neg = (labels == 0).astype(jnp.float32) * w
    n_pos = pos.sum()
    n_neg = neg.sum()
    u = (ranks * pos).sum() - n_pos * (n_pos + 1) / 2.0
    return u / jnp.maximum(n_pos * n_neg, 1.0)


def masked_mean(x: jnp.ndarray, weight: Optional[jnp.ndarray] = None
                ) -> jnp.ndarray:
    """Mean over valid entries (padded-eval masking helper)."""
    if weight is None:
        return x.mean()
    w = weight.astype(x.dtype)
    return (x * w).sum() / jnp.maximum(w.sum(), 1.0)
