"""The whole step's share of the chip's peak: operations the algorithm needs
(the benchmark's own count; three forward passes for a training row; no
recomputed operation, no padding row) for the rows of the trace's steady
span, over that span's length on the device's clock times chips times peak
FLOP/s.  The span holds whole periods of the loop, idle gaps included."""

from benchmark.lib import flops as F


def read(evidence, **_):
    traced, peak = evidence.get("traced"), evidence.get("peak")
    if not traced or not peak or not evidence.get("trace"):
        return None
    counts = evidence["flop_counts"]
    per_row = F.train_flops_per_row(counts) if evidence["mode"] == "train" \
        else counts["forward_flops"]
    return 100.0 * per_row * traced["rows"] / (
        traced["wall_s"] * evidence["chips"] * peak["bf16_flops_per_s"])
