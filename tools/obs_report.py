#!/usr/bin/env python
"""Summarize a run's telemetry JSONL into the INPUT_BENCH/PERF table shape.

The live telemetry (obs/) and the offline bench docs (INPUT_BENCH.md,
PERF.md) should speak one vocabulary — imgs/s, ms/step,
MFU, wait fractions — so a run's in-flight numbers drop straight into the
same tables the chip-gated verification items use.  Usage::

    python tools/obs_report.py <run_dir | telemetry.jsonl>        # summary
    python tools/obs_report.py <run_dir> --tail 5                 # raw tail
    python tools/obs_report.py <run_dir> --events                 # lifecycle

jax-free: reads through deepfake_detection_tpu.obs.events only (the obs
package lazy-imports its jax-touching modules), so this works as a cheap
reporting subprocess next to a running job.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepfake_detection_tpu.obs.events import iter_records  # noqa: E402


def _resolve(path: str) -> list:
    """Telemetry files for a run: the file itself, or — for a run dir —
    every ``telemetry*.jsonl`` in it (the trainer writes ONE
    ``telemetry.jsonl``; backfill workers write one
    ``telemetry-<worker>.jsonl`` EACH, and the report merges them)."""
    if os.path.isdir(path):
        import glob as _glob
        found = sorted(_glob.glob(os.path.join(path, "telemetry*.jsonl")))
        if not found:
            raise SystemExit(f"no telemetry log under {path}")
        return found
    if not os.path.isfile(path):
        raise SystemExit(f"no telemetry log at {path}")
    return [path]


def _read_all(paths: list) -> list:
    """All records of a run, merged across worker files in time order."""
    recs = [rec for p in paths for rec in iter_records(p)]
    recs.sort(key=lambda r: r.get("t") or 0)
    return recs


def _fmt(v, nd=1):
    if v is None:
        return "-"
    return f"{v:.{nd}f}"


def _epoch_rows(metrics):
    """Aggregate metrics records per epoch (weighted by window steps via
    the monotonic counters where available, else record-average)."""
    by_epoch = {}
    for m in metrics:
        by_epoch.setdefault(int(m.get("epoch", 0)), []).append(m)
    rows = []
    for epoch in sorted(by_epoch):
        recs = by_epoch[epoch]
        n = len(recs)

        def avg(key):
            vals = [r[key] for r in recs if r.get(key) is not None]
            return sum(vals) / len(vals) if vals else None

        rows.append({
            "epoch": epoch, "records": n,
            "imgs_per_s": avg("imgs_per_s"), "step_ms": avg("step_ms"),
            "data_wait_frac": avg("data_wait_frac"),
            "device_wait_frac": avg("device_wait_frac"),
            "host_frac": avg("host_frac"), "mfu": avg("mfu"),
            "loss": recs[-1].get("loss"),
        })
    return rows


def _quantile(sorted_vals, q: float):
    """Nearest-rank quantile of an ascending list."""
    return sorted_vals[min(int(q * len(sorted_vals)), len(sorted_vals) - 1)]


def _step_lines(metrics) -> list:
    """The per-step table: p50 / p95 / max of the period and of each phase
    over every ``steps`` row of the run (ms), then the slow steps by the
    phase they were filed under (the latest record's counters)."""
    fields = next((m["step_fields"] for m in metrics
                   if m.get("step_fields")), None)
    rows = [r for m in metrics for r in m.get("steps") or ()]
    if not fields or not rows:
        return []
    out = [f"\nsteps: {len(rows)} rows (ms)",
           "| field | p50 | p95 | max |", "|---|---|---|---|"]
    for i, name in enumerate(fields):
        if name in ("update", "batch"):
            continue
        vals = sorted(r[i] for r in rows)
        out.append(f"| {name} | {_fmt(_quantile(vals, 0.5), 3)} "
                   f"| {_fmt(_quantile(vals, 0.95), 3)} "
                   f"| {_fmt(vals[-1], 3)} |")
    last = metrics[-1].get("counters", {})
    slow = int(last.get("slow_steps_total", 0))
    by = ", ".join(f"{k[len('slow_steps_'):-len('_total')]} {int(v)}"
                   for k, v in sorted(last.items())
                   if k.startswith("slow_steps_") and k != "slow_steps_total"
                   and v)
    out.append(f"slow steps: {slow} of {int(last.get('steps_judged_total', 0))}"
               f" judged, {last.get('slow_step_excess_seconds_total', 0.0):.3f}"
               f"s over their neighbours' median" + (f" ({by})" if by else ""))
    return out


def summarize_backfill(path, metrics, events) -> None:
    """The backfill shape of the report: per-shard progress/throughput
    (runners/backfill.py emits one metrics record per committed or
    abandoned shard) plus the run_end books line — same vocabulary as
    BACKFILL_BENCH.md."""
    print(f"# {path}: backfill — {len(metrics)} shard records, "
          f"{len(events)} events")
    start = next((e for e in events if e.get("event") == "run_start"),
                 None)
    if start is not None:
        print(f"manifest: {start.get('num_clips')} clips / "
              f"{start.get('shards_total')} shards "
              f"(fingerprint {str(start.get('fingerprint'))[:12]}…), "
              f"batch {start.get('batch_size')}, "
              f"worker {start.get('worker')}")
    print()
    if metrics:
        print("| shard | clips | scored | failed | resumed | clips/s | "
              "data-wait | device-wait | host | recompiles |")
        print("|---|---|---|---|---|---|---|---|---|---|")
        for m in metrics:
            print(f"| {m.get('shard')} | {m.get('clips')} "
                  f"| {m.get('scored')} | {m.get('failed')} "
                  f"| {m.get('resumed')} | {_fmt(m.get('clips_per_s'))} "
                  f"| {_fmt(m.get('data_wait_s'), 2)}s "
                  f"| {_fmt(m.get('device_wait_s'), 2)}s "
                  f"| {_fmt(m.get('host_s'), 2)}s "
                  f"| {m.get('backend_compiles', 0)} |")
    steals = [e for e in events if e.get("event") == "lease_steal"]
    for e in steals:
        print(f"\nlease steal: {e.get('shard')} re-leased from dead "
              f"worker {e.get('prev_owner')}")
    end = next((e for e in reversed(events)
                if e.get("event") == "run_end"), None)
    if end is not None:
        b = end.get("books") or {}
        verdict = "BALANCED" if b.get("balanced") else (
            "incomplete" if not b.get("complete") else "IMBALANCED")
        print(f"\nbooks: {b.get('manifest_clips')} manifest == "
              f"{b.get('scored')} scored + {b.get('failed')} failed "
              f"+ {b.get('skipped_dup', 0)} skipped_dup — "
              f"{verdict} ({b.get('shards_done')}/"
              f"{b.get('shards_total')} shards done); this worker "
              f"{end.get('clips_this_proc')} clips @ "
              f"{_fmt(end.get('clips_per_s'))} clips/s, "
              f"{end.get('steady_recompiles')} steady-state recompiles")


def summarize(paths: list) -> None:
    path = paths[0] if len(paths) == 1 else \
        f"{os.path.dirname(paths[0])} ({len(paths)} worker streams)"
    metrics, events = [], []
    for rec in _read_all(paths):
        (metrics if rec.get("type") == "metrics" else events).append(rec)
    if not metrics and not events:
        raise SystemExit(f"{path}: no records")
    if any("shard" in m for m in metrics) or any(
            e.get("mode") == "backfill" for e in events
            if e.get("event") == "run_start"):
        summarize_backfill(path, metrics, events)
        return
    print(f"# {path}: {len(metrics)} metrics records, "
          f"{len(events)} events")
    # the mesh line (ISSUE 12): which topology the run compiled for — the
    # MFU denominator is mesh.size chips, so throughput numbers are only
    # comparable per mesh shape
    start = next((e for e in events if e.get("event") == "run_start"), None)
    if start is not None and start.get("mesh_shape"):
        shape = start["mesh_shape"]
        axes = start.get("axis_names") or []
        n = 1
        for s in shape:
            n *= int(s)
        print("mesh: "
              + " × ".join(f"{a}={s}" for a, s in zip(axes, shape))
              + f" ({n} device{'s' if n != 1 else ''})")
    print()
    if metrics:
        print("| epoch | imgs/s | ms/step | data-wait | device | host | "
              "mfu | loss |")
        print("|---|---|---|---|---|---|---|---|")
        for r in _epoch_rows(metrics):
            print(f"| {r['epoch']} | {_fmt(r['imgs_per_s'])} "
                  f"| {_fmt(r['step_ms'])} "
                  f"| {_fmt((r['data_wait_frac'] or 0) * 100)}% "
                  f"| {_fmt((r['device_wait_frac'] or 0) * 100)}% "
                  f"| {_fmt((r['host_frac'] or 0) * 100)}% "
                  f"| {_fmt(r['mfu'], 4) if r['mfu'] else '-'} "
                  f"| {_fmt(r['loss'], 4)} |")
        last = metrics[-1].get("counters", {})
        interesting = {k: v for k, v in last.items()
                       if v and not k.endswith("seconds_total")}
        if interesting:
            print("\ncounters (latest):")
            for k, v in sorted(interesting.items()):
                print(f"  {k} = {int(v) if float(v).is_integer() else v}")
        # where the augment milliseconds live: host chain (fetch seconds)
        # vs device prologue (stage-block seconds) — the --augment-device
        # before/after pivot.  The JSONL records carry counters only, so
        # the pivot keys off the elided-stages counter (> 0 from the
        # first drain of a device-augment run — stages are counted at
        # stage time, before any step drains); the
        # input_train_augment_path_device gauge is the /metrics-scraper
        # twin of the same fact.
        elided = last.get("input_train_host_augment_stages_elided_total", 0)
        if "input_train_batches_total" in last:
            hw = last.get("input_train_host_wait_seconds_total", 0.0)
            sb = last.get("input_train_stage_block_seconds_total", 0.0)
            aug_path = "device" if elided else "host"
            line = (f"\ninput augment path: {aug_path} "
                    f"(host stages elided: {int(elided)}; "
                    f"host-wait {hw:.1f}s, prologue stage-block {sb:.1f}s")
            if "input_train_h2d_block_seconds_total" in last:
                line += " = copy {:.1f}s + prologue {:.1f}s".format(
                    last["input_train_h2d_block_seconds_total"],
                    last.get("input_train_prologue_block_seconds_total", 0.0))
            parts = [(k, last.get(f"input_train_{k}_seconds_total"))
                     for k in ("load", "collate", "mixup")]
            if all(v is not None for _, v in parts):
                line += (f", host fetch {sum(v for _, v in parts):.1f}s = "
                         + " + ".join(f"{k} {v:.1f}s" for k, v in parts))
            print(line + ")")
        for line in _step_lines(metrics):
            print(line)
    resil = [e for e in events if e.get("event") in
             ("rewind", "preempted", "resume")]
    if resil:
        print("\nresilience events:")
        for e in resil:
            extra = {k: v for k, v in e.items()
                     if k not in ("v", "t", "type", "event")}
            print(f"  {e['event']}: {extra}")


def show_events(paths: list) -> None:
    for rec in _read_all(paths):
        if rec.get("type") == "event":
            print(json.dumps(rec))


def show_tail(paths: list, n: int) -> None:
    for rec in _read_all(paths)[-n:]:
        print(json.dumps(rec))


def main(argv=None) -> None:
    p = argparse.ArgumentParser(
        description="summarize a training run's telemetry JSONL")
    p.add_argument("path", help="run dir or telemetry.jsonl")
    p.add_argument("--tail", type=int, default=0, metavar="N",
                   help="print the last N raw records instead")
    p.add_argument("--events", action="store_true",
                   help="print lifecycle events only")
    args = p.parse_args(argv)
    paths = _resolve(args.path)
    if args.tail:
        show_tail(paths, args.tail)
    elif args.events:
        show_events(paths)
    else:
        summarize(paths)


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:       # `obs_report ... | head` is a normal use
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
