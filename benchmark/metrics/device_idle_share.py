"""Share of the trace's steady span in which no operation ran on the device:
1 - (union of the device-operation intervals) / span, both on the device's
own clock (``benchmark/lib/trace.py:steady_span``)."""


def read(evidence, **_):
    red = evidence.get("trace")
    if not red or red.get("busy_s", 0) <= 0 or red.get("window_s", 0) <= 0:
        return None
    return 100.0 * max(0.0, 1.0 - red["busy_s"] / red["window_s"])
