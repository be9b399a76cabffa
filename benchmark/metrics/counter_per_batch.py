"""One of the program's ``*_seconds_total`` counters per batch of the window:
the counter's rise from the window's start to its end (in a traced run, to
the trace's start) over the rise of the batch counter, times ``scale``
(1000: milliseconds a batch).  Nothing where the program has no such
counter or the window staged no batch."""


def read(evidence, counter, batches, scale=1.0, **_):
    c0, c1 = evidence.get("counters0"), evidence.get("counters1")
    if not c0 or not c1 or counter not in c1 or batches not in c1:
        return None
    n = c1[batches] - c0.get(batches, 0.0)
    if n <= 0:
        return None
    return scale * (c1[counter] - c0.get(counter, 0.0)) / n
