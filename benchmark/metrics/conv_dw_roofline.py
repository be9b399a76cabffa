"""Roofline share of the depthwise convolutions of a training step: the
least time the chip could take for them (the larger of FLOPs over peak and
bytes over peak bandwidth, from shapes; bytes bind) over the device time of
the trace's operations whose framework path names ``conv_dw``.  Returns
nothing where the trace carries no module path."""

from benchmark.lib import flops as F


def read(evidence, **_):
    red, traced, peak = (evidence.get("trace"), evidence.get("traced"),
                         evidence.get("peak"))
    if not red or not traced or not peak or not red.get("has_paths"):
        return None
    t = red["by_group"].get("conv_dw", 0.0)
    if t <= 0:
        return None
    floor = F.dw_train_floor_seconds(evidence["flop_counts"], traced["rows"],
                                     peak)
    return 100.0 * floor["seconds"] / t
