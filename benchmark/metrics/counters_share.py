"""The rise of several of the program's counters, summed, over the rise of
another, times ``scale`` (100: a share in percent), from the window's start
to its end (in a traced run, to the trace's start).  Nothing where the
program lacks any of the counters or the denominator did not rise."""


def read(evidence, counters, over, scale=1.0, **_):
    c0, c1 = evidence.get("counters0"), evidence.get("counters1")
    if not c0 or not c1 or any(k not in c1 for k in (*counters, over)):
        return None
    rise = c1[over] - c0.get(over, 0.0)
    if rise <= 0:
        return None
    return scale * sum(c1[k] - c0.get(k, 0.0) for k in counters) / rise
