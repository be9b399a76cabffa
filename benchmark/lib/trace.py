"""Reduction of a profiler trace (``*.xplane.pb``) to numbers.

Reads with ``jax.profiler.ProfileData`` only.  A TPU trace has one plane per
chip (``/device:TPU:<n>``); its ``XLA Ops`` line holds one event per executed
HLO operation and ``XLA Modules`` one per program launch.  Busy time is the
union of the operation intervals, so nested or overlapping events are counted
once, and both it and the window are taken on the device's own clock over
the steady span of the trace (``steady_span``): the host's clock, and what
starting and stopping the profiler costs the host, stay out of them.  Each event's statistics are searched for the framework's operation
path (``tf_op`` / ``op_name`` / ``long_name``...), which is where a flax
module's name (``conv_dw``, ``bn1``, ``se``...) shows if the compiler kept it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

_PATH_STATS = ("tf_op", "op_name", "name_scope", "long_name", "hlo_op",
               "source", "framework_op")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _union(intervals: List[Tuple[float, float]]) -> Tuple[float, List]:
    """Total covered length and the gaps between merged intervals."""
    if not intervals:
        return 0.0, []
    intervals.sort()
    total, gaps = 0.0, []
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    total += cur_e - cur_s
    return total, gaps


def _event_path(ev) -> str:
    found = ""
    for k, v in ev.stats:
        if k in _PATH_STATS and isinstance(v, str) and "/" in v \
                and len(v) > len(found):
            found = v
    return found


def device_planes(pd) -> List[Any]:
    planes = [p for p in pd.planes if re.match(r"/device:TPU:\d+$", p.name)]
    return planes or [p for p in pd.planes
                      if p.name.startswith("/device:") and "TPU" in p.name
                      and "Core" not in p.name]


def read_device_events(path: str) -> Dict[str, Any]:
    """{"chips": [{"ops": [(start_s, dur_s, name, path)], ...}]} — times in
    seconds on the trace's own clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips = []
    for plane in device_planes(pd):
        ops, modules, steps = [], [], []
        for line in plane.lines:
            lname = line.name
            if lname == "XLA Ops":
                for ev in line.events:
                    ops.append((ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                                ev.name, _event_path(ev)))
            elif lname == "XLA Modules":
                for ev in line.events:
                    modules.append((ev.start_ns * 1e-9,
                                    ev.duration_ns * 1e-9, ev.name))
        chips.append({"plane": plane.name, "ops": ops, "modules": modules})
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns >= 2_000_000 and \
                            not line.name.startswith("pjrt"):  # >= 2 ms
                        host.append((ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9,
                                     f"{line.name}:{ev.name}"))
    return {"chips": chips, "host": host}


def group_of(path: str, groups=()) -> str:
    """The module group of a framework path: the first of the
    configuration's ``trace_groups`` ([name, regular expression], most
    specific first) that the path matches, else ``other``."""
    for name, pattern in groups:
        if re.search(pattern, path):
            return name
    return "other"


def hlo_paths(hlo_text: str) -> Dict[str, str]:
    """{instruction name: framework path} from a compiled module's text.
    The chip's trace names each event by its HLO instruction and carries no
    framework path; the compiled program's own text does, in each
    instruction's ``metadata={op_name="..."}``."""
    out = {}
    for m in re.finditer(
            r"^\s*(?:ROOT )?(%[\w.\-]+) = .*?metadata=\{[^}]*?"
            r"op_name=\"([^\"]*)\"", hlo_text, re.M):
        out.setdefault(m.group(1), m.group(2))
    return out


def _short(name: str) -> str:
    """``%fusion.28 = (...) fusion(...)`` -> ``%fusion.28``."""
    return name.split(" = ", 1)[0]


def steady_span(modules: List[Tuple[float, float, str]]):
    """The steady part of a chip's trace, on the device's own clock: from
    the second launch of the program that takes most of the device's time
    (the step) to its last launch.  That span holds launches - 2 whole
    periods of the loop, each with one step's work and the gap after it,
    whatever the host's clock or the profiler's start and stop did at the
    edges.  The first launch is left out because the trace may have begun
    in the middle of it (its event is then cut short).  None where that
    program was launched fewer than four times."""
    by_name: Dict[str, List[float]] = {}
    for start, dur, name in modules:
        by_name.setdefault(_module_name(name), []).append((start, dur))
    if not by_name:
        return None
    name = max(by_name, key=lambda k: sum(d for _, d in by_name[k]))
    starts = sorted(s for s, _ in by_name[name])
    if len(starts) < 4:
        return None
    return {"module": name, "t0": starts[1], "t1": starts[-1],
            "steps": len(starts) - 2}


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce_events(ev: Dict[str, Any], paths: Optional[Dict[str, str]] = None,
                  groups=()) -> Dict[str, Any]:
    """busy_s and window_s (averaged over chips) of the steady span, the
    steps it holds, device time by operation and by module group inside it,
    each program's launches, and the longest idle gaps with what the host
    did.  ``paths`` maps an instruction's name to its framework path where
    the trace itself carries none; ``groups`` are the configuration's
    ``trace_groups``."""
    chips = ev["chips"]
    paths = paths or {}
    if not chips or not any(c["ops"] for c in chips):
        return {}
    busy, window, steps, by_name, by_group, other = [], [], [], {}, {}, {}
    gaps_all: List[Tuple[float, float]] = []
    has_paths, step_module = False, None
    for c in chips:
        ops = c["ops"]
        if not ops:
            continue
        span = steady_span(c["modules"])
        if span is not None:
            t0, t1 = span["t0"], span["t1"]
            ops = [o for o in ops if t0 <= o[0] < t1]
            steps.append(span["steps"])
            step_module = span["module"]
        else:
            t0 = min(s for s, _, _, _ in ops)
            t1 = max(s + d for s, d, _, _ in ops)
        iv = [(s, min(s + d, t1)) for s, d, _, _ in ops]
        total, gaps = _union(iv)
        if iv:
            first, last = min(s for s, _ in iv), max(e for _, e in iv)
            gaps = ([(t0, first)] if first > t0 else []) + gaps + \
                ([(last, t1)] if t1 > last else [])
        busy.append(total)
        window.append(t1 - t0)
        gaps_all.extend(gaps)
        for s, d, name, path in ops:
            name = _short(name)
            path = path or paths.get(name, "")
            by_name[name] = by_name.get(name, 0.0) + d
            has_paths = has_paths or bool(path)
            g = group_of(path, groups)
            by_group[g] = by_group.get(g, 0.0) + d
            if g == "other":
                key = "/".join(path.split("/")[-2:]) if path else \
                    re.sub(r"[.\d]+$", "", name)
                other[key] = other.get(key, 0.0) + d
    n = len(busy)
    modules: Dict[str, List[float]] = {}
    for c in chips:
        span = steady_span(c["modules"])
        for start, d, name in c["modules"]:
            # whole launches only: the trace may cut the first and the last
            if span is None or span["t0"] <= start < span["t1"]:
                modules.setdefault(_module_name(name), []).append(d)
    host = sorted(ev.get("host", []))
    idle = []
    for s, e in sorted(gaps_all, key=lambda g: g[0] - g[1])[:10]:
        what = "no host span (the program has no TraceAnnotation)"
        best = 0.0
        for hs, hd, hname in host:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best:
                best, what = ov, hname
        idle.append([what, e - s])
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy) / n, "window_s": sum(window) / n,
            "steps": (sum(steps) / len(steps)) if steps else None,
            "step_module": step_module,
            "chips": n, "device_ops": [[k, v / n] for k, v in top],
            "by_group": {k: v / n for k, v in by_group.items()},
            "other_top": [[k, v / n] for k, v in sorted(
                other.items(), key=lambda kv: -kv[1])[:8]],
            "modules": {k: {"count": len(v), "mean_s": sum(v) / len(v)}
                        for k, v in modules.items()},
            "has_paths": has_paths, "idle_gaps": idle}


def reduce_trace(trace_dir: str, paths: Optional[Dict[str, str]] = None,
                 groups=()) -> Dict[str, Any]:
    path = find_xplane(trace_dir)
    if path is None:
        return {}
    return reduce_events(read_device_events(path), paths, groups)


# Only XLA's operations and program launches on the device.  Under the
# profiler's default device mode every launch of a device-bound step waited
# 1.8 s (B4, b=80: 37 s for a 3.5 s epoch; PERF.md section 6).
DEVICE_MODE = {"tpu_trace_mode": "TRACE_ONLY_XLA"}


def start(trace_dir: str, host_level: int = 1, advanced=DEVICE_MODE) -> None:
    """Start a trace without the Python tracer: it hooks every call of the
    host's threads, and the host is what feeds the chip."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = host_level
    if advanced:
        opts.advanced_configuration = dict(advanced)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
