"""Roofline share of the sparse-attention indexer of a training step: its
needed operations (``lib/flops_dsa.py``'s ``dsa_index``: its projections,
and its scores forward over every causal pair) at the chip's peak, over the
device time of the trace's operations in the groups ``dsa_index`` (the
projections) and ``dsa_select`` (the selection kernel, which makes every
causal pair's score).  The scores' backward runs in the attention's
kernels and is counted and timed under ``attn_sparse``.  Nothing where the
trace has none of them or the counts are of another family."""

from benchmark.lib import flops_seq as F


def read(evidence, counted=("dsa_index",),
         timed=("dsa_index", "dsa_select"), **_):
    red, traced, peak = (evidence.get("trace"), evidence.get("traced"),
                         evidence.get("peak"))
    counts = evidence.get("flop_counts") or {}
    if not red or not traced or not peak or \
            any(g not in counts for g in counted):
        return None
    t = sum(red["by_group"].get(g, 0.0) for g in timed)
    if t <= 0:
        return None
    return 100.0 * F.group_train_floor_seconds(
        counts, counted, traced["rows"], peak) / t
