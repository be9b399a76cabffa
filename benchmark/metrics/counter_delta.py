"""The rise of one of the program's counters over the window (in a traced
run, up to the trace's start).  Nothing where the program has no such
counter."""


def read(evidence, counter, **_):
    c0, c1 = evidence.get("counters0"), evidence.get("counters1")
    if not c0 or not c1 or counter not in c0 or counter not in c1:
        return None
    return c1[counter] - c0[counter]
