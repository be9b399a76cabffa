"""What tracing costs a training cell, variant by variant, in one process.

    python3 benchmark/tests/trace_probe.py <workload> <seed> <variant,...>

Builds the cell as a run does, warms it through epoch 0, times one untraced
epoch, then for each variant runs one epoch with ``trace_steps`` launches
traced from ``trace_from_step`` and prints one JSON line: the epoch's and
the traced steps' host seconds, what stopping the profiler cost, the trace's
size, the steady span on the device's clock with its busy seconds, and the
periods between successive launches of the step.  Variants: ``xla`` (the
benchmark's own options: device trace mode TRACE_ONLY_XLA), ``default_mode``
(the profiler's default device mode), ``h0`` (no host tracer), ``s4`` (four
launches only).  Run on the chip; the
benchmark's own runs do not run it.
"""

import json
import os
import shutil
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

VARIANTS = {
    "xla": {},
    "default_mode": {"advanced": None},
    "h0": {"host_level": 0},
    "s4": {"steps": 4},
}


def main(argv) -> None:
    import jax
    from benchmark.drivers import train as D
    from benchmark.lib import manifest as M
    from benchmark.lib import trace as TR
    from deepfake_detection_tpu.train import train_one_epoch
    workload, seed = argv[0], int(argv[1])
    names = argv[2].split(",") if len(argv) > 2 else list(VARIANTS)
    man = M.load_json(os.environ["BENCHMARK_MANIFEST"]) \
        if os.environ.get("BENCHMARK_MANIFEST") else None
    cell = M.Cell(workload, man)
    if os.environ.get("BENCHMARK_ALLOW_CPU") != "1":
        D.require_chips(cell.chips)
    D.setup_cache(cell.cache_dir)
    out_dir = os.path.join(cell.cache_dir, "probe")
    shutil.rmtree(out_dir, ignore_errors=True)
    built = D.Built(cell, out_dir)
    batch = built.global_batch
    dataset, variables, spec = D.make_inputs(cell, seed, batch)
    state = built.state_for(variables)
    loader, _, _ = built.loader_for(dataset, seed % (2 ** 31 - 1), 0)
    rng = built.rng_for(seed)
    step = D.StepTap(built.train_step)
    step.calls = D.CHECK_STEPS          # a plain call from the first step on

    def epoch(e, state):
        loader.set_epoch(e)
        t = time.monotonic()
        state, _ = train_one_epoch(
            e, step, state, loader, built.cfg, jax.random.fold_in(rng, e),
            lr_scheduler=built.lr_scheduler, saver=None, output_dir="",
            world_size=built.n_dev)
        return state, time.monotonic() - t

    state, _ = epoch(0, state)
    spe = len(loader)
    state, plain_s = epoch(1, state)
    print(json.dumps({"variant": "untraced", "epoch_s": plain_s,
                      "steps_per_epoch": spe, "batch": batch}), flush=True)
    mix = cell.traffic
    real_start = TR.start
    e = 1
    for name in names:
        v = VARIANTS[name]
        e += 1
        trace_dir = os.path.join(cell.cache_dir, "probe_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        TR.start = lambda d, v=v: real_start(
            d, host_level=v.get("host_level", 1),
            advanced=v.get("advanced", TR.DEVICE_MODE))
        step.traced = None
        first = min(int(mix["trace_from_step"]), spe // 3)
        step.plan_trace(step.calls + first,
                        min(int(v.get("steps", mix["trace_steps"])),
                            spe - first - 1), trace_dir)
        line = {"variant": name}
        try:
            state, line["epoch_s"] = epoch(e, state)
            step.stop_trace()
            line.update(step.traced or {})
            path = TR.find_xplane(trace_dir)
            line["xplane_bytes"] = os.path.getsize(path) if path else 0
            ev = TR.read_device_events(path)
            red = TR.reduce_events(ev)
            line.update({k: red.get(k) for k in (
                "busy_s", "window_s", "steps", "step_module")})
            line["ops_events"] = sum(len(c["ops"]) for c in ev["chips"])
            starts = sorted(s for c in ev["chips"] for s, _, n in c["modules"]
                            if TR._module_name(n) == red.get("step_module"))
            periods = [b - a for a, b in zip(starts, starts[1:])]
            if periods:
                line["period_s"] = {"min": min(periods),
                                    "median": statistics.median(periods),
                                    "max": max(periods)}
            line["step_device_s"] = (red.get("modules") or {}).get(
                red.get("step_module"), {}).get("mean_s")
            line["idle_gaps"] = red.get("idle_gaps", [])[:4]
        except Exception as exc:            # a variant the profiler refuses
            line["error"] = repr(exc)[:300]
            step.stop_trace()
        step.sessions.clear()
        med = (line.get("period_s") or {}).get("median")
        line["cured"] = bool(med and line.get("step_device_s")
                             and med < 1.5 * line["step_device_s"])
        print(json.dumps(line), flush=True)
        if line["cured"] and os.environ.get("PROBE_STOP_AT_CURE") == "1":
            break
    TR.start = real_start
    loader.close()


if __name__ == "__main__":
    main(sys.argv[1:])
