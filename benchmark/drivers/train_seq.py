"""Training driver for sequence configurations of any family: one cell =
one configuration under one pool-of-documents mix.

``drivers/train_tokens.py``'s set-up / window / close / ``correct``
sequence, with its builder, taps, pool and comparison imported, and three
things taken from the files the configuration names instead of from a
family: the operation counts are ``forward_counts(config)`` of the
configuration's plain reference (``reference.module``); the planted model
faults are ``MODEL_FAULTS`` / ``faulty_model`` of the file
``reference.faults`` names; and the run's ``TrainTelemetry`` is handed the
model's own censuses (``attn_tiles_visited``, ``ssd_chunks``; 0 where the
model has none), as ``runners/train.py`` hands them.  Nothing here names a
model: the next sequence architecture brings a configuration, a reference
and a faults file.
"""

from __future__ import annotations

import importlib
import os
import shutil
import time
from typing import Any, Dict, Optional

from benchmark.drivers.train import (CHECK_STEPS, compare, judge,
                                     program_numbers, require_chips,
                                     setup_cache)
from benchmark.drivers.train_tokens import (STEP_FAULTS, PoolOfDocuments,
                                            TokenBuilt, TokenStepTap,
                                            make_inputs, reference_batches,
                                            reference_first_steps)
from benchmark.lib import manifest as M

__all__ = ["CHECK_STEPS", "STEP_FAULTS", "PoolOfDocuments", "TokenBuilt",
           "TokenStepTap", "census", "compare", "judge", "make_inputs",
           "model_faults", "program_numbers", "reference_batches",
           "reference_first_steps", "require_chips", "run", "setup_cache"]


def model_faults(config):
    """The module of the configuration's planted model faults."""
    path = os.path.splitext(config["reference"]["faults"])[0]
    return importlib.import_module(path.replace("/", "."))


def census(model, name: str, seq_len: int) -> int:
    """One of the model's per-row censuses; 0 where it has none."""
    count = getattr(model, name, None)
    return int(count(seq_len)) if count else 0


def run(cell: M.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, need_chip: bool = True,
        fault: Optional[str] = None) -> Dict[str, Any]:
    import jax
    from deepfake_detection_tpu.obs import (EventLog, TrainTelemetry,
                                            loader_collector)
    from deepfake_detection_tpu.train import train_one_epoch
    from benchmark import reference
    from benchmark.lib import trace as TR

    peak = require_chips(cell.chips) if need_chip else None
    setup_cache(cell.cache_dir)
    out_dir = os.path.join(cell.cache_dir, "out")
    shutil.rmtree(out_dir, ignore_errors=True)
    built = TokenBuilt(cell, out_dir)
    built.model = model_faults(cell.config).faulty_model(
        built.model, fault if fault not in STEP_FAULTS else None)
    cfg = built.cfg
    batch = built.global_batch
    dataset, variables, spec = make_inputs(cell, seed, batch)
    state = built.state_for(variables)
    loader_seed = seed % (2 ** 31 - 1)
    loader, host_tap = built.loader_for(dataset, loader_seed, CHECK_STEPS)
    rng = built.rng_for(seed)
    step = TokenStepTap(built.train_step,
                        fault=fault if fault in STEP_FAULTS else None)
    telemetry = TrainTelemetry(
        event_log=EventLog(os.path.join(out_dir, "telemetry.jsonl")),
        attn_tiles_per_sample=census(built.model, "attn_tiles_visited",
                                     cfg.seq_len),
        ssd_chunks_per_sample=census(built.model, "ssd_chunks", cfg.seq_len))
    telemetry.register_collector(loader_collector(loader))

    def epoch(e, state):
        loader.set_epoch(e)
        return train_one_epoch(
            e, step, state, loader, cfg, jax.random.fold_in(rng, e),
            lr_scheduler=built.lr_scheduler, saver=None, output_dir="",
            world_size=built.n_dev, telemetry=telemetry)

    # ---- set-up: compile, warm the loader, tap the first three steps ----
    trace_dir = os.path.join(cell.cache_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    steps_per_epoch = len(loader)
    snaps = {}
    if trace:
        # primed as in drivers/train.py: a process's first trace of a
        # device-bound loop stalls every launch, its second does not
        mix = cell.traffic
        at = int(mix["trace_from_step"])
        step.plan_trace(at, int(mix["trace_prime_steps"]),
                        trace_dir + "_prime", keep=False)
        step.plan_trace(int(mix["trace_epoch"]) * steps_per_epoch + at,
                        int(mix["trace_steps"]), trace_dir,
                        lambda: snaps.update(
                            at_trace=telemetry.snapshot()["counters"]))
    state, _ = epoch(0, state)
    step.stop_trace()
    setup_s = time.time() - t_start

    # ---- the window ----
    snap0 = telemetry.snapshot()["counters"]
    e, t0 = 0, time.monotonic()
    while time.monotonic() - t0 < seconds:
        e += 1
        state, last = epoch(e, state)
    step.stop_trace()
    window_s = time.monotonic() - t0
    snap_end = telemetry.snapshot()["counters"]
    snap1 = snaps.get("at_trace") or snap_end
    rows = e * steps_per_epoch * batch
    attempted, failed = e * steps_per_epoch, int(last.get("nonfinite", 0))

    # ---- close: memory, then free the program's state ----
    mem = jax.devices()[0].memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0)) + \
        int(mem.get("peak_bytes_reserved", 0))
    loader.close()
    telemetry.close()
    del state, loader, variables

    # ---- correct: the reference follows the tapped steps ----
    t_ref = time.monotonic()
    prog = program_numbers(step, cell.config)
    step.opt1 = None                       # 5.6 GB of host memory at 697M
    batches, numbers = reference_batches(dataset, host_tap)
    ref = reference_first_steps(cell.config, spec, step.params0, batches)
    numbers.update(compare(prog, ref))
    ok, compared = judge(numbers, cell.config["reference"]["limits"],
                         every_limit=True)
    reference_s = time.monotonic() - t_ref

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": built.n_dev, "memory_peak_bytes": peak_bytes}
    result: Dict[str, Any] = {
        "correct": bool(ok), "attempted": attempted, "failed": failed,
        "metrics": {}, "device": device}
    unit = {m["name"]: m["unit"] for m in cell.end_to_end()}
    if not trace:
        e2e = {"train_clips_per_s": rows / window_s, "setup_s": setup_s}
        result["metrics"] = {k: {"value": float(v), "unit": unit[k]}
                             for k, v in e2e.items() if k in unit}
    else:
        red, traced = {}, None
        if step.traced and step.arg_specs is not None:
            paths = TR.hlo_paths(built.train_step.lower(
                *step.arg_specs).compile().as_text())
            red = TR.reduce_trace(trace_dir, paths,
                                  cell.config.get("trace_groups", ()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red and red.get("steps"):
            traced = {"rows": red["steps"] * batch, "wall_s": red["window_s"]}
        evidence = {
            "peak": peak, "trace": red, "traced": traced,
            "counters0": snap0, "counters1": snap1,
            "flop_counts": reference.model(cell.config).forward_counts(
                cell.config), "mode": "train",
            "chips": built.n_dev}
        result["metrics"] = M.read_per_layer(cell, evidence)
        if red:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {
                "device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"],
                "by_group": sorted(([k, v] for k, v in
                                    red["by_group"].items()),
                                   key=lambda kv: -kv[1]),
                "other_top": red["other_top"]}
            result["traced"] = dict(step.traced, steps=red["steps"],
                                    step_module=red["step_module"])
    result["window"] = {
        "seconds": window_s, "epochs": e, "rows": rows,
        "rows_per_s": rows / window_s, "setup_s": setup_s,
        "tokens_per_s": rows * cfg.seq_len / window_s,
        "steps_per_epoch": steps_per_epoch, "batch": batch,
        "reference_s": reference_s,
        # programs built inside the window (the program's own counter over
        # the whole window): must be 0
        "compiles": snap_end.get("compiles_total", 0.0)
        - snap0.get("compiles_total", 0.0)}
    result["numbers"] = {k: float(v) for k, v in numbers.items()}
    result["compared"] = compared
    return result
