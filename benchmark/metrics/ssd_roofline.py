"""Roofline share of the state-space dual scans of a training step: the
least time the chip could take for them, forward and backward (the larger of
operations at peak and bytes at the memory's rate:
``lib/flops_ssd.py:scan_train_floor_seconds``), over the device time of the
trace's operations whose framework path names ``ssd_scan``.  Nothing where
the trace has no such group or the counts are of another family."""

from benchmark.lib import flops_ssd as F


def read(evidence, group="ssd_scan", **_):
    red, traced, peak = (evidence.get("trace"), evidence.get("traced"),
                         evidence.get("peak"))
    counts = evidence.get("flop_counts") or {}
    if not red or not traced or not peak or "ssd_elems" not in counts:
        return None
    t = red["by_group"].get(group, 0.0)
    if t <= 0:
        return None
    return 100.0 * F.scan_train_floor_seconds(
        counts, traced["rows"], peak)["seconds"] / t
