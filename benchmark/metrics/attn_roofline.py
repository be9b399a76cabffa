"""Roofline share of the attention mixers of a training step: the needed
operations of the named groups (their projections and the unmasked pairs,
three forward passes: ``lib/flops_seq.py``) at the chip's peak, over the
device time of the trace's operations in those groups.  Nothing where the
trace has none of them or the counts are of another family."""

from benchmark.lib import flops_seq as F


def read(evidence, groups, **_):
    red, traced, peak = (evidence.get("trace"), evidence.get("traced"),
                         evidence.get("peak"))
    counts = evidence.get("flop_counts") or {}
    if not red or not traced or not peak or \
            any(g not in counts for g in groups):
        return None
    t = sum(red["by_group"].get(g, 0.0) for g in groups)
    if t <= 0:
        return None
    return 100.0 * F.group_train_floor_seconds(
        counts, groups, traced["rows"], peak) / t
