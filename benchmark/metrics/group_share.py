"""Share of the steady span's busy time that the named groups of device
operations took: says whether they or the rest set the step.  Nothing where
the trace has none of them."""


def read(evidence, groups, **_):
    red = evidence.get("trace")
    if not red or red.get("busy_s", 0) <= 0:
        return None
    t = sum(red["by_group"].get(g, 0.0) for g in groups)
    if t <= 0:
        return None
    return 100.0 * t / red["busy_s"]
