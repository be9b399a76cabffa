"""The sum of the named program counters at the window's start: what they
gathered over all of set-up.  Nothing where the program lacks one of
them."""


def read(evidence, counters, **_):
    c0 = evidence.get("counters0")
    if not c0 or any(k not in c0 for k in counters):
        return None
    return sum(c0[k] for k in counters)
