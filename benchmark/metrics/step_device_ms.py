"""Mean device time of one launch of the named program (``module``, e.g.
``jit_step``), from the trace's ``XLA Modules`` line: what the chip takes for
a step whatever the host does around it."""


def read(evidence, module="jit_step", **_):
    red = evidence.get("trace") or {}
    m = (red.get("modules") or {}).get(module)
    if not m or m["mean_s"] <= 0:
        return None
    return 1e3 * m["mean_s"]
