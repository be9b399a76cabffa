"""Per-step training telemetry: time breakdown, throughput, MFU, catalog.

The tracker rides the trainer's existing metric-drain cadence and adds
**zero device syncs**: every input it receives is a host float the trainer
already materialized (the buffered ``float(m["loss"])`` reads at drain),
or a ``time.monotonic`` delta around work the loop already does.  The
breakdown attributes a drain window's wall time to three places:

* **data wait** — the loop blocked on ``next(loader)`` (host pipeline
  starving the chip); the per-step ``data_time`` the trainer logs.
* **device wait** — the loop blocked materializing the buffered metric
  scalars at the drain boundary (the device still executing its step
  backlog).  Because metric reads are the ONLY host syncs in the loop,
  this is the async-dispatch measurement of "the chip is the bottleneck".
* **host time** — the remainder: dispatch, collate hand-off, Python.

The :class:`~deepfake_detection_tpu.data.loader.DeviceLoader` double-buffer
boundaries add two more counters (``input_*``): time blocked in
``next()`` on the host loader and time blocked in the slab-recycle
``block_until_ready`` (prologue/staging backpressure) — both are waits the
loader already performed; the tracker only timestamps them.

**Steps are kept apart.**  Each :meth:`TrainTelemetry.on_step` writes one
row (the step's period and its phases: ``STEP_FIELDS``) into the drain
window; the drain's JSONL record carries the rows as ``steps``, and each
step is judged against the median of the 16 before it, so that slow steps
are counted, priced and filed under the phase that grew, in counters any
snapshot carries (no drain needed inside the interval that is read).

The process's compilations are counted too (``compiles_total`` and the
seconds jax spent tracing, lowering and in the backend's compiler): one
``jax.monitoring`` listener, installed when this module is imported so that
whatever is built before a registry exists is still on its books.

Throughput (img/s over the drain window) times the per-sample forward
FLOP count of ``obs/flops.py`` (× 3 for fwd+bwd, the standard training
approximation) against the device's peak rate gives a **live MFU gauge**.
The benchmark's ``step_mfu.train`` (``benchmark/``, PERF.md §2) is the
measured number; this one is the run's own dial.

Rendering goes through the shared :mod:`..utils.prometheus` text renderer
(the serving subsystem's ``GET /metrics`` sibling); obs/server.py exposes
it on ``--metrics-port``.  Each drain also appends one ``metrics`` record
to the run's JSONL event log (obs/events.py).
"""

from __future__ import annotations

import logging
import os
import statistics
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

from jax import monitoring

from ..utils.metrics import LatencyHistogram
from ..utils.prometheus import PromText

_logger = logging.getLogger(__name__)

__all__ = ["TrainTelemetry", "forward_flops_per_sample", "peak_flops",
           "loader_collector", "native_warp_collector",
           "resilience_collector", "STEP_FIELDS", "STEP_PHASES"]

_PREFIX = "dfd_train"

#: step histogram bounds: 1 ms .. 60 s (first-step compile tails land in the
#: top buckets; the drain record's ``steps`` rows have every step exactly)
_STEP_BOUNDS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

#: the phases of a step's period, the keys of ``on_step(phases=)``: seconds
#: blocked on the producer's queue, in device_put + prologue dispatch, on the
#: batch's host-to-device copy, on its prologue behind the running step (the
#: four are this step's rise of ``LoaderStats``), inside the step call, in
#: the drain's block, in ``_save_recovery``
STEP_PHASES = ("host_wait", "stage", "h2d_block", "prologue_block",
               "dispatch", "drain", "save")
#: a row of the drain record's ``steps``: identifiers, the period (hand-over
#: to hand-over, so the rows tile the epoch), the phases, and ``rest``, the
#: period less their sum (scheduler, heartbeat, logging, telemetry)
STEP_FIELDS = ("update", "batch", "period") + STEP_PHASES + ("rest",)
#: a step is judged against the median of ``period - drain`` (a drain's
#: block belongs to the steps it waited for, not to the iteration that paid
#: it) over the _REF_STEPS steps before it, once there are _REF_MIN of them
_REF_STEPS, _REF_MIN = 16, 8
#: ... slow above _SLOW x that median, short below _SHORT x it (the one or
#: two iterations after a drain, when the loop runs ahead into an empty
#: device queue), normal between
_SLOW, _SHORT = 1.25, 0.75
#: what a slow step can be filed under: every phase but the drain, and rest
_BLAME = tuple(k for k in STEP_FIELDS[3:] if k != "drain")
_I_PERIOD, _I_DRAIN = (STEP_FIELDS.index(k) for k in ("period", "drain"))
#: the phases summed into a ``step_<phase>_seconds_total`` counter as the
#: row is written: those a benchmark metric divides by a counter of the
#: loop's own steps and no counter on the loop's clock has yet (the loader's
#: ``input_*`` run up to an iteration ahead; host_wait, stage and the drain
#: are read from ``input_*`` and ``device_wait_seconds_total``)
_SUMMED_PHASES = ("h2d_block", "prologue_block", "dispatch")

# bf16 peak per chip by device_kind: the program's one peak table
_PEAK_FLOPS = {
    "TPU v2": 22.5e12, "TPU v3": 61.5e12 / 2, "TPU v4": 137.5e12 * 2,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v5": 229.5e12 * 2,
    "TPU v5p": 459e12, "TPU v6 lite": 918e12, "TPU v6e": 918e12,
    "TPU v7": 2307e12,
}

_COUNTER_CATALOG = (
    ("steps_total", "Train steps dispatched"),
    ("samples_total", "Training samples consumed"),
    ("train_tokens_total", "Tokens of the train steps dispatched (sequence "
     "models: rows x positions of each step's batch; 0 for image batches)"),
    ("attn_tiles_visited_total", "Grid cells of the flash-attention kernels "
     "(forward and the fused backward; forward, dK/dV and dQ where the "
     "backward is split) with a visible pair, over the train steps "
     "dispatched: rows x the model's per-row census (0 for image models). "
     "Attention's work goes with the square of --seq-len and with the "
     "kernels' blocks, train_tokens_total with neither: read the two rates "
     "together when either changes between runs"),
    ("ssd_chunks_total", "Chunks the state-space dual scan walked in "
     "sequence over the train steps dispatched: rows x the model's per-row "
     "census (its Mamba-2 layers x the chunks of a row; 0 for models "
     "without the scan).  The scan's sequential depth goes with --seq-len "
     "over the chunk, its work with --seq-len times the chunk"),
    ("moe_routed_tokens_total", "Tokens the routed expert layers of the "
     "train steps routed: tokens x expert layers, counted on the device "
     "(ops/moe.py:routing_counts), fetched with each step's metrics and "
     "added at the drain with the two counters below, so that their ratios "
     "are of like with like; 0 for models without such a layer"),
    ("moe_assignments_total", "(token, selected expert) assignments that "
     "fell on the experts this chip holds, summed the same way: over "
     "moe_routed_tokens_total, the assignments a routed token brings here "
     "(experts per token x held / all, were the routing uniform)"),
    ("moe_peak_assignments_total", "The fullest held expert's assignments "
     "of each expert layer, summed the same way"),
    ("moe_full_capacity_passes_total", "Passes (one expert layer, one "
     "microbatch) whose assignments on held experts passed the first "
     "capacity, so that the layer walked every row (ops/moe.py); summed "
     "the same way.  A pass is moe_routed_tokens_total over the tokens of "
     "a microbatch"),
    ("dsa_selected_pairs_total", "(query, key) pairs the learned sparse "
     "attention layers of the train steps selected (ops/sparse_attention.py"
     ": topk a query, every causal key of the first topk positions), counted "
     "on the device, fetched with each step's metrics and added at the "
     "drain with the two counters below; 0 for models without such a "
     "layer"),
    ("dsa_blocks_touched_total", "(128 query, 128 key) causal blocks that "
     "hold at least one selected pair, summed the same way: over "
     "dsa_blocks_causal_total, the share of the causal blocks a kernel that "
     "skips blocks without a selected pair would still have to visit (a "
     "census of the selection, data-dependent)"),
    ("dsa_blocks_causal_total", "(128 query, 128 key) blocks holding a "
     "causal pair, summed the same way"),
    ("drains_total", "Metric drain boundaries (telemetry records)"),
    ("step_seconds_total", "Wall seconds spent in the train loop"),
    ("step_h2d_block_seconds_total", "Seconds the loop's steps were blocked "
     "on their batch's host-to-device copy.  This and the two below are the "
     "step rows' phases summed as each row is written, so that their rise "
     "between two snapshots is of the same steps as steps_total's and "
     "step_seconds_total's (the loader's own input_* counters run up to one "
     "iteration ahead of the loop's).  The input pipeline starving the chip "
     "only where the device is idle too: a copy queued behind a running "
     "step waits here as well"),
    ("step_prologue_block_seconds_total", "... blocked on the batch's "
     "prologue, queued behind the running step (the chip is the "
     "bottleneck)"),
    ("step_dispatch_seconds_total", "... inside the train step's call (the "
     "body of the dfd.train.step span): argument handling and the launch, "
     "not the device's work"),
    ("recovery_save_seconds_total", "Seconds the loop thread spent in "
     "in-epoch recovery snapshots (the dfd.train.recovery_save span)"),
    ("steps_judged_total", "Steps with at least 8 steps before them, each "
     "judged by its period less its drain block against the median of that "
     "quantity over the 16 steps before it: normal within 0.75-1.25 x, "
     "slow above, short below (judged - normal - slow: the iterations "
     "after a drain, when the loop runs ahead into an empty device queue)"),
    ("normal_steps_total", "Judged steps within 0.75-1.25 x the median"),
    ("normal_step_seconds_total", "Their periods less their drain blocks: "
     "over normal_steps_total, the period of a step that is neither slow "
     "nor short"),
    ("slow_steps_total", "Judged steps above 1.25 x the median"),
    ("slow_step_excess_seconds_total", "The part of each slow step above "
     "the median: over step_seconds_total, the share of the loop's time "
     "lost to steps a quarter slower than their neighbours"),
) + tuple(
    (f"slow_steps_{k}_total", f"Slow steps filed under {k}: the phase "
     "whose own excess over its median in the same 16 steps is largest")
    for k in _BLAME) + (
    ("data_wait_seconds_total", "Seconds the loop blocked on next(loader)"),
    ("device_wait_seconds_total", "Seconds the drain blocked materializing "
     "buffered device scalars (device-bound time)"),
    ("nonfinite_steps_total", "Steps whose loss/grad-norm was non-finite"),
    ("guard_spike_steps_total", "Steps the anomaly guard flagged as loss "
     "spikes"),
    ("rewinds_total", "Guard rewinds to a recovery snapshot"),
    ("recovery_snapshots_total", "In-epoch recovery snapshots written"),
    ("preemptions_total", "Preemption stops honored at a step boundary"),
    ("profile_captures_total", "On-demand profiler trace windows captured"),
    ("watchdog_beats_total", "Stall-watchdog heartbeats received"),
    ("watchdog_near_misses_total", "Heartbeats older than 0.5x the "
     "watchdog timeout when they landed"),
    ("compiles_total", "Programs the backend built in this process (a load "
     "from the persistent cache counts: a program was built either way)"),
    ("jax_trace_seconds_total", "Seconds jax spent tracing functions to "
     "jaxprs"),
    ("jax_lower_seconds_total", "Seconds jax spent lowering jaxprs to MLIR "
     "modules"),
    ("backend_compile_seconds_total", "Seconds in the backend's compiler "
     "(or loading its output from the persistent cache)"),
)

# jax.monitoring duration events -> the counter each feeds.  Process-wide
# totals, because a compilation belongs to the process and most of them
# happen in set-up, before any registry is built.
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace_seconds_total",
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "jax_lower_seconds_total",
    "/jax/core/compile/backend_compile_duration":
        "backend_compile_seconds_total",
}
_compile_totals = {"compiles_total": 0.0,
                   **{k: 0.0 for k in _COMPILE_EVENTS.values()}}
_compile_lock = threading.Lock()        # warm-ups compile on several threads
_compile_roots = threading.local()      # per thread: name -> [(start, secs)]


def _on_compile_event(event: str, duration_secs: float, **_kw) -> None:
    name = _COMPILE_EVENTS.get(event)
    if name is None:
        return
    # jax traces a jit that is called inside another's trace and reports
    # both, the inner one first (5000 events for one EfficientNet step, a
    # third of their sum nested): an event's seconds count less those of
    # the events of its kind that ended on this thread since it began
    roots = _compile_roots.__dict__.setdefault(name, [])
    start = time.monotonic() - duration_secs
    own = duration_secs
    while roots and roots[-1][0] >= start:
        own -= roots.pop()[1]
    roots.append((start, duration_secs))
    if len(roots) > 8192:               # top-level events only pile up
        del roots[:4096]
    with _compile_lock:
        _compile_totals[name] += max(own, 0.0)
        if name == "backend_compile_seconds_total":
            _compile_totals["compiles_total"] += 1


monitoring.register_event_duration_secs_listener(_on_compile_event)


def _compile_collector() -> Dict[str, Dict[str, float]]:
    with _compile_lock:
        return {"counters": dict(_compile_totals)}


def backend_compile_count() -> int:
    """Programs the backend built in this process since this module was
    imported: the serving side's zero-recompile probes read differences of
    it (``serving/metrics.py`` re-exports it)."""
    with _compile_lock:
        return int(_compile_totals["compiles_total"])

_GAUGE_CATALOG = (
    ("up", "1 while the trainer's telemetry is live"),
    ("epoch", "Current epoch"),
    ("update", "Global update counter at the last drain"),
    ("loss", "Train loss, epoch-running average at the last drain (the "
     "trainer log line's avg — spikes show in nonfinite/spike counters)"),
    ("prec1", "Train top-1 precision, epoch-running average at the last "
     "drain"),
    ("learning_rate", "Current learning rate"),
    ("throughput_imgs_per_s", "Images/sec over the last drain window"),
    ("step_time_ms", "Mean step wall time over the last drain window"),
    ("step_time_p50_ms", "Median step period of the last drain window "
     "(exact: from the window's rows)"),
    ("step_time_max_ms", "Longest step period of the last drain window"),
    ("data_wait_frac", "Fraction of the last window blocked on input"),
    ("device_wait_frac", "Fraction of the last window blocked on the "
     "device backlog"),
    ("host_frac", "Fraction of the last window in host-side dispatch"),
    ("mfu", "Live model FLOPs utilization (0 when peak rate unknown, "
     "e.g. CPU)"),
    ("model_fwd_gflops_per_sample", "Per-sample forward GFLOPs feeding "
     "the MFU gauge (obs/flops.py)"),
    ("dw_grad_kernel_stages", "Depthwise stages of the train step whose "
     "filter gradient is the reduction kernel (ops/conv.py: "
     "dw_grad_impl decides from shapes, dtype and device count; a census "
     "fixed by the shapes)"),
    ("dw_grad_xla_stages", "Depthwise stages of the train step that keep "
     "XLA's own filter gradient"),
    ("causal_conv_kernel_layers", "Mamba layers of the train step whose "
     "causal convolution runs as the two TPU kernels (ops/causal_conv.py: "
     "causal_conv_impl decides from the row's length, the channels and the "
     "backend; a census fixed by the shapes)"),
    ("causal_conv_xla_layers", "Mamba layers of the train step whose causal "
     "convolution takes the array form"),
    ("attn_fused_bwd_layers", "Attention layers of the train step whose "
     "backward is the one fused kernel (ops/flash_attention.py: fused_bwd "
     "decides from the row's length and the head's width; a census fixed "
     "by the shapes)"),
    ("attn_split_bwd_layers", "Attention layers of the train step whose "
     "backward is the dK/dV and dQ pair of kernels"),
    ("attn_fwd_saved_layers", "Rematerialised attention layers of a "
     "sequence model's train step whose backward reuses the forward "
     "kernel's saved output and row statistics instead of running it again "
     "(ops/flash_attention.py:saved_fwd_census, from the layers and the "
     "remat policy; a census: ViT and TimeSformer are not counted)"),
    ("mla_layers", "Layers of the train step with multi-head latent "
     "attention (models/glm4moelite.py; a census fixed by the model)"),
    ("dsa_layers", "Layers of the train step with learned sparse "
     "attention (models/keyevl2.py; a census fixed by the model)"),
    ("dsa_fwd_bits_layers", "Learned sparse attention layers of the train "
     "step whose attention forward reads the selection kernel's packed "
     "bits, one a (query, key) pair, instead of making the index scores "
     "again (ops/sparse_attention.py; a census: every layer on the "
     "kernels, none under the array form)"),
    ("dsa_kl_loss", "The sparse-attention indexer's loss, KL of the heads' "
     "mean attention over the selected keys from the indexer's softmax, "
     "mean over rows and layers, over the steps of the last drain (0 "
     "before any)"),
    ("moe_load_peak_to_mean", "The fullest held expert's assignments over "
     "the held experts' mean, over the steps of the last drain that routed "
     "(0 before any)"),
    ("restart_count", "Restart-wrapper relaunches of this run "
     "(DFD_RESTART_COUNT)"),
    ("watchdog_beat_age_s", "Seconds since the last watchdog heartbeat"),
)


class TrainTelemetry:
    """One registry per training process.

    Hot-path contract: :meth:`on_step` and :meth:`on_drain` take host
    floats only and never touch a ``jax.Array`` — the overhead-guard test
    asserts a telemetry-on run performs exactly the device syncs a
    telemetry-off run does.
    """

    def __init__(self, event_log: Optional[Any] = None,
                 flops_per_sample: float = 0.0,
                 peak_flops: float = 0.0,
                 meta: Optional[Dict[str, Any]] = None,
                 attn_tiles_per_sample: int = 0,
                 ssd_chunks_per_sample: int = 0,
                 dw_grad_stages: Tuple[int, int] = (0, 0),
                 causal_conv_layers: Tuple[int, int] = (0, 0),
                 attn_bwd_layers: Tuple[int, int] = (0, 0),
                 attn_fwd_saved_layers: int = 0,
                 mla_layers: int = 0,
                 dsa_layers: int = 0,
                 dsa_fwd_bits_layers: int = 0):
        self.event_log = event_log
        self.flops_per_sample = float(flops_per_sample)
        # attention-kernel cells a step visits per row: a sequence model's
        # attn_tiles_visited(seq_len); 0 for images
        self.attn_tiles_per_sample = int(attn_tiles_per_sample)
        # chunks the scan walks per row: a model's ssd_chunks(seq_len)
        self.ssd_chunks_per_sample = int(ssd_chunks_per_sample)
        self.peak = float(peak_flops)
        self.meta = dict(meta or {})
        self.profiler = None          # optional obs.profiler.ProfilerCapture
        self._lock = threading.RLock()
        self._c: "OrderedDict[str, float]" = OrderedDict()
        self._g: "OrderedDict[str, float]" = OrderedDict()
        self._help: Dict[str, str] = {}
        for name, help_ in _COUNTER_CATALOG:
            self._c[name] = 0.0
            self._help[name] = help_
        for name, help_ in _GAUGE_CATALOG:
            self._g[name] = 0.0
            self._help[name] = help_
        self._g["up"] = 1.0
        self._g["model_fwd_gflops_per_sample"] = round(
            self.flops_per_sample / 1e9, 3)
        # (kernel, xla): the program's census of its depthwise stages
        self._g["dw_grad_kernel_stages"] = float(dw_grad_stages[0])
        self._g["dw_grad_xla_stages"] = float(dw_grad_stages[1])
        # (kernels, array form): a model's causal_conv_layers(seq_len)
        self._g["causal_conv_kernel_layers"] = float(causal_conv_layers[0])
        self._g["causal_conv_xla_layers"] = float(causal_conv_layers[1])
        # (fused, split): a model's attn_bwd_layers(seq_len)
        self._g["attn_fused_bwd_layers"] = float(attn_bwd_layers[0])
        self._g["attn_split_bwd_layers"] = float(attn_bwd_layers[1])
        self._g["attn_fwd_saved_layers"] = float(attn_fwd_saved_layers)
        self._g["mla_layers"] = float(mla_layers)
        self._g["dsa_layers"] = float(dsa_layers)
        self._g["dsa_fwd_bits_layers"] = float(dsa_fwd_bits_layers)
        self._g["restart_count"] = float(
            os.environ.get("DFD_RESTART_COUNT", 0) or 0)
        self.h_step = LatencyHistogram(_STEP_BOUNDS)
        self._collectors: List[Callable[[], Dict[str, Dict[str, float]]]] = [
            _compile_collector]
        # drain-window accumulators (single-writer: the train loop).  The
        # window length is the SUM of per-step wall times, not a monotonic
        # anchor: per-step wall (trainer batch_time) already covers the
        # loop end-to-end including data wait and the drain block, so the
        # breakdown fractions are consistent by construction and the
        # tracker is a pure function of its inputs (testable without
        # sleeping).
        self._win_steps = 0
        self._win_samples = 0
        self._win_wall = 0.0
        self._win_data_wait = 0.0
        # ... and the window's steps kept apart: one STEP_FIELDS row a step
        # (seconds), cleared at the drain
        self._rows: List[tuple] = []
        # the _REF_STEPS steps before this one: period - drain of each, and
        # its row (read for a slow step only)
        self._ref_q: "deque[float]" = deque(maxlen=_REF_STEPS)
        self._ref_rows: "deque[tuple]" = deque(maxlen=_REF_STEPS)

    # -- registry ------------------------------------------------------
    def inc(self, name: str, n: float = 1.0) -> None:
        with self._lock:
            self._c[name] = self._c.get(name, 0.0) + n

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._g[name] = value

    def register_collector(
            self, fn: Callable[[], Dict[str, Dict[str, float]]]) -> None:
        """``fn`` returns ``{"counters": {...}, "gauges": {...}}`` of
        already-monotonic totals / current values; called at every drain
        and render so names appear in the catalog from registration on."""
        self._collectors.append(fn)
        self._run_collectors()

    def _run_collectors(self) -> None:
        for fn in self._collectors:
            try:
                out = fn()
            except Exception as e:          # noqa: BLE001 — never kill a run
                _logger.warning("telemetry collector failed: %r", e)
                continue
            with self._lock:
                for k, v in out.get("counters", {}).items():
                    self._c[k] = float(v)
                for k, v in out.get("gauges", {}).items():
                    self._g[k] = float(v)

    # -- hot-loop hooks ------------------------------------------------
    def on_step(self, n_samples: int, data_wait_s: float,
                step_wall_s: float, tokens: int = 0, *, update: int = -1,
                batch: int = -1, period: Optional[float] = None,
                phases: Optional[Mapping[str, float]] = None) -> None:
        """Once per loop iteration; host floats only.  ``tokens``: rows x
        positions of a sequence batch (``--seq-len``), 0 for images;
        ``update`` / ``batch`` the identifiers the step's spans carry;
        ``period`` the row's period, hand-over to hand-over (left out:
        ``step_wall_s``, which ends at the same hand-over and starts after
        the previous iteration's tail); ``phases`` the seconds of the period
        spent in each of ``STEP_PHASES``, by name (a phase left out is 0, so
        a caller with no loop to take apart gets all of the period as
        ``rest``)."""
        if period is None:
            period = step_wall_s
        phases = phases or {}
        ph = tuple(phases.get(k, 0.0) for k in STEP_PHASES)
        row = (update, batch, period) + ph + (period - sum(ph),)
        self._rows.append(row)
        self._win_steps += 1
        self._win_wall += step_wall_s
        self._win_samples += int(n_samples)
        self._win_data_wait += data_wait_s
        self.h_step.observe(step_wall_s)
        # the verdict on this step, from the steps before it
        q = period - row[_I_DRAIN]
        ref = statistics.median(self._ref_q) \
            if len(self._ref_q) >= _REF_MIN else 0.0
        slow = ref > 0.0 and q > _SLOW * ref
        if slow:
            over = [row[i] - statistics.median(r[i] for r in self._ref_rows)
                    for i in map(STEP_FIELDS.index, _BLAME)]
            filed = _BLAME[over.index(max(over))]
        self._ref_q.append(q)
        self._ref_rows.append(row)
        with self._lock:
            c = self._c
            c["steps_total"] += 1
            c["samples_total"] += n_samples
            c["train_tokens_total"] += tokens
            c["attn_tiles_visited_total"] += \
                n_samples * self.attn_tiles_per_sample
            c["ssd_chunks_total"] += \
                n_samples * self.ssd_chunks_per_sample
            c["step_seconds_total"] += step_wall_s
            c["data_wait_seconds_total"] += data_wait_s
            for k in _SUMMED_PHASES:
                c[f"step_{k}_seconds_total"] += phases.get(k, 0.0)
            if ref > 0.0:
                c["steps_judged_total"] += 1
            if slow:
                c["slow_steps_total"] += 1
                c["slow_step_excess_seconds_total"] += q - ref
                c[f"slow_steps_{filed}_total"] += 1
            elif ref > 0.0 and q >= _SHORT * ref:
                c["normal_steps_total"] += 1
                c["normal_step_seconds_total"] += q

    def on_routing(self, tokens: int, assignments: int, peak: int,
                   peak_filled: int, full_passes: int) -> None:
        """Once per drain that fetched routing counts, with their sums over
        the drained steps (ops/moe.py:routing_counts; host ints)."""
        with self._lock:
            self._c["moe_routed_tokens_total"] += tokens
            self._c["moe_assignments_total"] += assignments
            self._c["moe_peak_assignments_total"] += peak
            self._c["moe_full_capacity_passes_total"] += full_passes
            if assignments:
                self._g["moe_load_peak_to_mean"] = round(
                    peak_filled / assignments, 4)

    def on_sparse_attention(self, selected: int, touched: int, causal: int,
                            kl_loss: float) -> None:
        """Once per drain that fetched a sparse-attention census, with its
        sums over the drained steps (ops/sparse_attention.py) and the
        indexer's loss, their mean; host numbers."""
        with self._lock:
            self._c["dsa_selected_pairs_total"] += selected
            self._c["dsa_blocks_touched_total"] += touched
            self._c["dsa_blocks_causal_total"] += causal
            self._g["dsa_kl_loss"] = kl_loss

    def on_drain(self, *, epoch: int, batch_idx: int, num_updates: int,
                 loss: float, prec1: float, lr: float,
                 drain_wait_s: float, nonfinite_steps: int = 0) -> None:
        """Once per drain boundary, AFTER the trainer materialized the
        buffered scalars (``drain_wait_s`` is how long that block took;
        ``nonfinite_steps`` is this window's bad-step count)."""
        wall = max(self._win_wall, 1e-9)
        steps, samples = self._win_steps, self._win_samples
        if steps == 0:
            return
        data_wait = self._win_data_wait
        rows = self._rows
        periods = [r[_I_PERIOD] for r in rows]
        imgs_per_s = samples / wall
        mfu = 0.0
        if self.peak > 0 and self.flops_per_sample > 0:
            mfu = imgs_per_s * self.flops_per_sample * 3.0 / self.peak
        with self._lock:
            self._c["drains_total"] += 1
            self._c["device_wait_seconds_total"] += drain_wait_s
            self._c["nonfinite_steps_total"] += max(int(nonfinite_steps), 0)
            g = self._g
            g["epoch"] = float(epoch)
            g["update"] = float(num_updates)
            g["loss"] = float(loss)
            g["prec1"] = float(prec1)
            g["learning_rate"] = float(lr)
            g["throughput_imgs_per_s"] = round(imgs_per_s, 3)
            g["step_time_ms"] = round(wall / steps * 1e3, 3)
            g["step_time_p50_ms"] = round(statistics.median(periods) * 1e3, 3)
            g["step_time_max_ms"] = round(max(periods) * 1e3, 3)
            g["data_wait_frac"] = round(min(data_wait / wall, 1.0), 4)
            g["device_wait_frac"] = round(min(drain_wait_s / wall, 1.0), 4)
            g["host_frac"] = round(
                max(1.0 - (data_wait + drain_wait_s) / wall, 0.0), 4)
            g["mfu"] = round(mfu, 4)
        self._run_collectors()
        if self.event_log is not None:
            with self._lock:
                counters = dict(self._c)
                gauges = {k: v for k, v in self._g.items()
                          if k not in ("up",)}
            self.event_log.metrics(
                epoch=epoch, batch=batch_idx, update=num_updates,
                imgs_per_s=round(imgs_per_s, 3),
                step_ms=gauges["step_time_ms"],
                data_wait_frac=gauges["data_wait_frac"],
                device_wait_frac=gauges["device_wait_frac"],
                host_frac=gauges["host_frac"],
                loss=float(loss), prec1=float(prec1), lr=float(lr),
                mfu=gauges["mfu"], counters=counters,
                # the window's steps, one STEP_FIELDS row each, in ms
                step_fields=list(STEP_FIELDS),
                steps=[[r[0], r[1]] + [round(v * 1e3, 3) for v in r[2:]]
                       for r in rows])
        # reset the window
        self._rows = []
        self._win_steps = 0
        self._win_samples = 0
        self._win_wall = 0.0
        self._win_data_wait = 0.0

    # -- lifecycle -----------------------------------------------------
    def event(self, name: str, **fields: Any) -> None:
        if name == "rewind":
            self.inc("rewinds_total")
        elif name == "preempted":
            self.inc("preemptions_total")
        elif name == "profile_capture":
            self.inc("profile_captures_total")
        if self.event_log is not None:
            self.event_log.event(name, **fields)

    def close(self) -> None:
        self.set_gauge("up", 0.0)
        if self.event_log is not None:
            self.event_log.close()

    # -- exposition ----------------------------------------------------
    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """One consistent view of the whole registry."""
        self._run_collectors()
        with self._lock:
            return {"counters": dict(self._c), "gauges": dict(self._g)}

    def render_prometheus(self) -> str:
        snap = self.snapshot()
        doc = PromText(_PREFIX)
        for name, value in snap["counters"].items():
            doc.counter(name, self._help.get(name, name), _num(value))
        for name, value in snap["gauges"].items():
            doc.gauge(name, self._help.get(name, name), _num(value))
        doc.histogram("step_seconds", "Per-step wall time", self.h_step)
        return doc.render()


def _num(v: float):
    """Integral values render without a trailing .0 (counter idiom)."""
    return int(v) if float(v).is_integer() else v


# ---------------------------------------------------------------------------
# MFU inputs
# ---------------------------------------------------------------------------

def peak_flops(device=None) -> float:
    """Per-chip bf16 peak for the MFU denominator.

    0.0 on a non-TPU platform (the gauge then reads 0 rather than a
    meaningless ratio); a TPU whose ``device_kind`` is not in the table
    raises — a peak is never assumed for a chip nobody looked up."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != "tpu":
        return 0.0
    peak = {k.lower(): v for k, v in _PEAK_FLOPS.items()}.get(
        device.device_kind.lower())
    if peak is not None:
        return peak
    raise ValueError(
        f"no bf16 peak recorded for TPU device_kind {device.device_kind!r}: "
        f"add it to obs/telemetry.py:_PEAK_FLOPS with its source")


def forward_flops_per_sample(model, variables, input_shape) -> float:
    """Per-sample forward FLOPs via the jaxpr walk of :mod:`.flops`.

    ``input_shape`` is the (1, H, W, C) shape the LOADER feeds the model
    (already pixel-shuffled under ``--stem-s2d``); ``variables`` may be
    abstract.  Returns 0.0 when the walk fails — the MFU gauge then stays
    0 instead of lying.
    """
    import jax
    import jax.numpy as jnp

    from .flops import analyze
    x = jax.ShapeDtypeStruct(tuple(input_shape), jnp.float32)
    try:
        buckets, _, _ = analyze(model, variables, x,
                                in_chans=int(input_shape[-1]))
    except Exception as e:              # noqa: BLE001 — telemetry is optional
        _logger.warning("forward-FLOPs analysis failed (%r); "
                        "MFU gauge disabled", e)
        return 0.0
    return float(sum(buckets.values()))


# ---------------------------------------------------------------------------
# Collectors: input pipeline, native warp, resilience
# ---------------------------------------------------------------------------

def loader_collector(device_loader, name: str = "train"):
    """Input-pipeline counters/gauges off a DeviceLoader and its host
    loader (thread or shm backend) — attribute reads only, no locking
    against the producer (floats are single-writer, torn reads impossible
    under the GIL)."""

    def collect() -> Dict[str, Dict[str, float]]:
        st = device_loader.stats
        c = {
            f"input_{name}_batches_total": st.batches,
            f"input_{name}_host_wait_seconds_total": st.host_wait_s,
            # with --augment-device on this block is ALSO where the
            # prologue's augment compute surfaces to the host (the only
            # wait on the prologue output): the per-drain breakdown's
            # attribution of "where the augment milliseconds live"
            f"input_{name}_stage_block_seconds_total": st.stage_block_s,
            # ... its two halves: the batch's host-to-device copy
            # (starvation only where the device is idle too), then its
            # prologue behind the running step (the chip's backlog)
            f"input_{name}_h2d_block_seconds_total": st.h2d_block_s,
            f"input_{name}_prologue_block_seconds_total":
                st.prologue_block_s,
            # device_put + prologue dispatch of each batch (host time)
            f"input_{name}_stage_seconds_total": st.stage_s,
            # samples x host-chain stages (warp/blur/mixup-blend) elided
            # by device-side augmentation
            f"input_{name}_host_augment_stages_elided_total":
                getattr(st, "augment_elided", 0),
        }
        g: Dict[str, float] = {
            # 1 = the train augment renders on device (--augment-device
            # on), 0 = host chain — the /metrics-scraper pivot; the JSONL
            # log carries counters only, so tools/obs_report.py keys the
            # same fact off the elided-stages counter above
            f"input_{name}_augment_path_device":
                1.0 if getattr(device_loader, "augment_device", False)
                else 0.0,
        }
        host = device_loader.loader
        hstats = getattr(host, "stats", None)
        if hstats is not None:           # thread backend producer stats
            # load (the pool's decode+transform), collate (stack), mixup
            # (the uint8 blend): the producer's three phases of a fetch
            c[f"input_{name}_load_seconds_total"] = hstats.load_s
            c[f"input_{name}_collate_seconds_total"] = hstats.collate_s
            c[f"input_{name}_mixup_seconds_total"] = hstats.mixup_s
            c[f"input_{name}_backpressure_seconds_total"] = hstats.put_wait_s
        if hasattr(host, "ring_depth"):  # shm backend
            c[f"input_{name}_worker_respawns_total"] = host.respawn_count
            c[f"input_{name}_ring_stall_sweeps_total"] = getattr(
                host, "stall_sweeps", 0)
            workers = [p for p in getattr(host, "_workers", [])
                       if p is not None]
            g[f"input_{name}_workers_alive"] = float(
                sum(1 for p in workers if p.is_alive())) if workers else 0.0
            depth = float(host.ring_depth)
            g[f"input_{name}_ring_occupancy"] = round(
                min(getattr(host, "inflight_batches", 0) / depth, 1.0), 4)
        return {"counters": c, "gauges": g}

    return collect


def native_warp_collector():
    """Fused-warp source-copy counters (data/native.py): elided = packed
    mmap views handed to the strided kernel with no ``ascontiguousarray``
    copy; copied = frames that still needed the contiguous staging copy."""

    def collect() -> Dict[str, Dict[str, float]]:
        from ..data import native
        stats = native.warp_copy_stats()
        return {"counters": {
            "input_warp_src_copies_elided_total": stats["elided"],
            "input_warp_src_copies_total": stats["copied"],
        }, "gauges": {}}

    return collect


def resilience_collector(resilience):
    """Fault-layer counters off a train.resilience.Resilience handle."""

    def collect() -> Dict[str, Dict[str, float]]:
        c: Dict[str, float] = {}
        g: Dict[str, float] = {}
        guard = resilience.guard
        if guard is not None:
            c["guard_spike_steps_total"] = guard.spike_total
        wd = resilience.watchdog
        if wd is not None:
            c["watchdog_beats_total"] = wd.beats_total
            c["watchdog_near_misses_total"] = wd.near_miss_total
            g["watchdog_beat_age_s"] = round(wd.beat_age(), 3)
        return {"counters": c, "gauges": g}

    return collect
