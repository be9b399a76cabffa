"""Roofline share of the selective scans of a training step: the least time
the chip could take for them, forward and backward (bytes bind:
``lib/flops_seq.py:scan_train_floor_seconds``), over the device time of the
trace's operations whose framework path names ``mamba_scan``.  Nothing
where the trace has no such group or the counts are of another family."""

from benchmark.lib import flops_seq as F


def read(evidence, group="mamba_scan", **_):
    red, traced, peak = (evidence.get("trace"), evidence.get("traced"),
                         evidence.get("peak"))
    counts = evidence.get("flop_counts") or {}
    if not red or not traced or not peak or "scan_elems" not in counts:
        return None
    t = red["by_group"].get(group, 0.0)
    if t <= 0:
        return None
    return 100.0 * F.scan_train_floor_seconds(
        counts, traced["rows"], peak)["seconds"] / t
