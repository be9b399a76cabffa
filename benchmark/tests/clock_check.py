"""Are the program's spans on the device trace's clock?

    python3 benchmark/tests/clock_check.py <workload> <seed> [<seconds>]

Runs the cell as ``run.py --trace 1`` does and, before the run deletes its
trace, reads the profiler's file once more: for each traced launch of the
step program (``XLA Modules`` of the first chip's plane) the offset from
the start of its ``dfd.train.step`` span (host plane) to the start of the
launch on the device.  The session starts and stops inside a call of the
step, so the first launch's span began before the session and is not in
the file: spans and launches are matched from the last backwards.  A span
that starts after its launch (a negative offset) means two clocks.  Also
prints, for every ``dfd.*`` span name, how many the trace holds and their
median and total milliseconds, and the same for the ten other host events
of 2 ms or more that take most time (what the runtime's own threads were
doing).  The line ``{"clock_check": ...}`` follows
the run's result line.  Run on the chip; the benchmark's own runs do not
run it.
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def read_clock(path: str, module: str = "jit_step") -> dict:
    """Offsets (ms) of span start to launch start, and the spans by name."""
    from jax.profiler import ProfileData
    from benchmark.lib import trace as TR
    pd = ProfileData.from_file(path)
    launches = []
    for plane in TR.device_planes(pd)[:1]:
        for line in plane.lines:
            if line.name == "XLA Modules":
                launches = sorted(
                    ev.start_ns for ev in line.events
                    if TR._module_name(ev.name) == module)
    steps, by_name, others = [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("dfd."):
                        by_name.setdefault(ev.name, []).append(
                            ev.duration_ns * 1e-6)
                        if ev.name == "dfd.train.step":
                            steps.append(ev.start_ns)
                    elif ev.duration_ns >= 2_000_000:
                        others.setdefault(ev.name, []).append(
                            ev.duration_ns * 1e-6)
    steps.sort()
    n = min(len(steps), len(launches))
    offsets = [(l - s) * 1e-6 for s, l in zip(steps[len(steps) - n:],
                                              launches[len(launches) - n:])]
    def table(groups):
        return {k: {"count": len(v), "median_ms": statistics.median(v),
                    "total_ms": sum(v)} for k, v in groups}

    out = {"launches": len(launches), "step_spans": len(steps),
           "spans": table(sorted(by_name.items())),
           "other_host_events": table(sorted(
               others.items(), key=lambda kv: -sum(kv[1]))[:10])}
    if offsets:
        out["offset_ms"] = {"min": min(offsets),
                            "median": statistics.median(offsets),
                            "max": max(offsets)}
        out["offsets_ms"] = offsets
        out["negative"] = sum(o < 0 for o in offsets)
    return out


def main(argv) -> int:
    from benchmark import run as R
    from benchmark.lib import trace as TR
    seen = {}
    reduce_trace = TR.reduce_trace

    def reduce_and_read(trace_dir, *args, **kw):
        path = TR.find_xplane(trace_dir)
        if path:
            seen.update(read_clock(path))
        return reduce_trace(trace_dir, *args, **kw)

    TR.reduce_trace = reduce_and_read
    try:
        rc = R.main(["--workload", argv[0], "--seed", argv[1], "--seconds",
                     argv[2] if len(argv) > 2 else "30", "--trace", "1"])
    finally:
        TR.reduce_trace = reduce_trace
    print(json.dumps({"clock_check": seen}))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
