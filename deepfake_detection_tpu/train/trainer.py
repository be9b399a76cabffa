"""Epoch-level training and validation loops.

Parity with the reference runner's ``train_epoch`` (``/root/reference/dfd/
runners/train.py:594-700``) and ``validate`` (:703-767): the same meters, the
same log line (loss/prec1 val(avg), s/batch, s/image, LR, data time, ETA),
``--save-images`` batch dumps, in-epoch recovery checkpoints, per-update LR
scheduling, and mixup-off-epoch switching.  What disappears on TPU: the
explicit ``torch.cuda.synchronize`` (the runner only blocks when it reads the
logged scalars — JAX async dispatch keeps the device busy) and the per-step
metric allreduce (it lives inside the compiled step).
"""

from __future__ import annotations

import functools
import logging
import os
import signal
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, annotate_function

from ..obs.profiler import start_trace
from ..utils.metrics import AverageMeter, auc
from .resilience import Preempted, RewindRequested
from .state import TrainState, get_learning_rate, set_learning_rate

_logger = logging.getLogger(__name__)

__all__ = ["train_one_epoch", "validate", "save_image_batch"]


def save_image_batch(x, path: str, img_num: int = 4) -> None:
    """Dump a normalized NHWC batch as a tiled jpg (reference :679-684).

    Frames of each clip are laid out horizontally, batch vertically; values
    min-max normalized like torchvision's ``save_image(normalize=True)``.
    """
    from PIL import Image
    a = np.asarray(x, np.float32)
    lo, hi = a.min(), a.max()
    a = (a - lo) / max(hi - lo, 1e-6)
    b, h, w, c = a.shape
    assert c % img_num == 0
    cpf = c // img_num
    frames = a.reshape(b, h, w, img_num, cpf).transpose(0, 3, 1, 2, 4)
    grid = frames.reshape(b, img_num * h, w, cpf).transpose(1, 0, 2, 3) \
        .reshape(img_num * h, b * w, cpf)
    if cpf == 1:
        grid = np.repeat(grid, 3, axis=-1)
    Image.fromarray((grid[..., :3] * 255).astype(np.uint8)).save(path)


def train_one_epoch(epoch: int, train_step: Callable, state: TrainState,
                    loader, cfg, rng: jax.Array,
                    lr_scheduler=None, saver=None, output_dir: str = "",
                    meta: Optional[Dict[str, Any]] = None,
                    world_size: int = 1, start_batch: int = 0,
                    resilience=None, telemetry=None):
    """One epoch of the hot loop.  Returns ``(state, metrics)``.

    ``world_size`` is the data-parallel degree; s/image in the log line is
    per-device (the reference's ``bs`` is the per-GPU batch, train.py:658).

    ``start_batch`` > 0 resumes MID-epoch: the caller has already
    fast-forwarded the loader to that batch (loaders are deterministic in
    ``(seed, epoch, batch_index)``, so the stream is bit-identical to an
    uninterrupted epoch) and this loop restores the absolute batch index /
    update count so step RNG folding and LR scheduling continue exactly.

    ``resilience`` (train/resilience.py) hooks the loop into the fault-
    tolerance layer: per-step watchdog heartbeats, the preemption stop
    check at step boundaries (synchronous recovery snapshot + ``Preempted``),
    the NaN/spike guard fed at drain cadence (may raise ``RewindRequested``),
    and the env-gated chaos injection points the recovery tests drive.

    ``telemetry`` (obs/telemetry.py TrainTelemetry) rides the same
    cadences with host floats only — per-step wall/data-wait deltas and,
    at each drain, the time the drain itself blocked (the device-bound
    share) — so enabling it adds NO device syncs; its optional
    ``.profiler`` (obs/profiler.py) gets a per-step window check and a
    per-drain trigger-file poll for on-demand trace capture.

    The loop writes three spans into any open profiler session, on the
    device trace's clock: ``dfd.train.step`` (a step annotation carrying
    ``step_num``, around the dispatch), ``dfd.train.drain`` and
    ``dfd.train.recovery_save``.  All its timers read ``time.monotonic``,
    the clock of the loader's counters they are divided by.

    With no session open the same facts reach ``telemetry.on_step`` as one
    row a step: the period (from the previous iteration's hand-over to
    this one's, so the periods tile the epoch: what follows the hand-over
    -- logging, the drain record, a recovery snapshot, the scheduler, the
    heartbeat -- is in the next row) and the seconds of it spent in each of
    ``obs/telemetry.py:STEP_PHASES``: this step's rise of the loader's four
    counters, two clock reads around the step call, the drain's and the
    snapshot's own deltas.  The step's wall time it hands beside the row
    (``batch_time_m.val``, what ``step_seconds_total`` sums) is as it was:
    it ends at the same hand-over and leaves the previous iteration's tail
    out.
    """
    if cfg.mixup > 0 and hasattr(loader, "mixup_enabled"):
        if cfg.mixup_off_epoch and epoch >= cfg.mixup_off_epoch:
            loader.mixup_enabled = False    # reference :597-599

    batch_time_m, data_time_m = AverageMeter(), AverageMeter()
    losses_m, prec1_m = AverageMeter(), AverageMeter()

    end = time.monotonic()
    num_batches = len(loader)
    last_idx = num_batches - 1
    num_updates = epoch * num_batches + start_batch
    nonfinite_total = 0
    lr = get_learning_rate(state)
    # a token dataset's rows (--seq-len): their positions count as tokens
    seq_len = getattr(cfg, "seq_len", 0)
    chaos = getattr(resilience, "chaos", None)
    if chaos is not None and not chaos.active:
        chaos = None

    # jax.profiler window (SURVEY §5: the reference has no profiler; an MFU
    # target can't be tuned blind).  Steps [start, start+N) of epoch 0 are
    # traced into <output_dir>/profile — view with TensorBoard or Perfetto.
    # rank-0 only: with a collective (sharded) saver output_dir is set on
    # every rank, but trace/image side effects must not race on shared FS
    profile_n = getattr(cfg, "profile", 0) if epoch == 0 and output_dir \
        and jax.process_index() == 0 else 0
    profile_start = min(10, max(num_batches - profile_n, 0))
    profiling = False

    # Device-side metric scalars are buffered and only materialized at log
    # boundaries: a float() on every step would block the host on each
    # step's completion and serialize dispatch, forfeiting the async-
    # dispatch overlap that replaces the reference's CUDA-stream prefetch.
    # Consequence: batch_time_m.val at a log step absorbs the wait for the
    # whole buffered backlog (so .avg is the accurate number); the plateau
    # scheduler sees a loss avg that is up to log_interval steps stale.
    pending: list = []
    step_exec = None       # multi-process: AOT executable (_compile_aligned)
    first_step = True
    # telemetry window accumulators: how long drains blocked (device-bound
    # time) and how many buffered steps were bad, since the last record
    drain_wait_acc = 0.0
    drain_bad_acc = 0
    profiler = getattr(telemetry, "profiler", None)
    # the step row: where the last period ended, what the loader's counters
    # read there, and the drain / snapshot seconds since (see the docstring)
    t_row = end
    lstats = getattr(loader, "stats", None)
    if not hasattr(lstats, "prologue_block_s"):
        lstats = None       # a loader that keeps no LoaderStats
    loader_s = _loader_seconds(lstats)
    drain_row = save_row = 0.0

    @functools.partial(annotate_function, name="dfd.train.drain")
    def _drain() -> None:
        nonlocal nonfinite_total, drain_wait_acc, drain_bad_acc, drain_row
        t_drain = time.monotonic()
        window_bad = 0
        routing = None         # a routed model's counts, summed on the way
        sparse, kl = None, []  # a sparse-attention model's census and loss
        for m, n, step_i in pending:
            loss_value = float(m["loss"])     # host sync, log steps only
            # the device-side guard flag (loss OR grad-norm non-finite)
            # rides the same fetch; absent when the guard is off
            bad = not np.isfinite(loss_value)
            if "nonfinite" in m:
                bad = bad or float(m["nonfinite"]) > 0
            if bad:
                nonfinite_total += 1
                window_bad += 1
                _logger.warning(
                    "non-finite training step at update %d (loss %r%s)",
                    step_i, loss_value,
                    "; update skipped" if "nonfinite" in m else
                    "; UPDATE APPLIED (guard off)")
            else:
                losses_m.update(loss_value, n)
            prec1_m.update(float(m["prec1"]), n)
            if "moe_counts" in m:
                counts = np.asarray(m["moe_counts"], np.int64)
                routing = counts if routing is None else routing + counts
            if "dsa_counts" in m:
                counts = np.asarray(m["dsa_counts"], np.int64)
                sparse = counts if sparse is None else sparse + counts
                kl.append(float(m["aux_loss"]))
            if resilience is not None:
                # may raise RewindRequested after K consecutive bad steps
                resilience.observe_step(step_i, loss_value, bad)
        pending.clear()
        # the scalar reads above are the loop's ONLY host syncs, so their
        # block time IS the device-bound share of the window
        waited = time.monotonic() - t_drain
        drain_wait_acc += waited
        drain_row += waited
        drain_bad_acc += window_bad
        if routing is not None and telemetry is not None:
            telemetry.on_routing(*(int(c) for c in routing))
        if sparse is not None and telemetry is not None:
            telemetry.on_sparse_attention(*(int(c) for c in sparse),
                                          kl_loss=float(np.mean(kl)))

    def _snapshot(batch_idx: int, sync: bool = False) -> None:
        nonlocal save_row
        t_save = time.monotonic()
        _save_recovery(saver, state, meta, epoch, batch_idx, num_updates,
                       sync=sync)
        saved = time.monotonic() - t_save
        save_row += saved
        if telemetry is not None:
            telemetry.inc("recovery_snapshots_total")
            telemetry.inc("recovery_save_seconds_total", saved)

    for batch_idx, batch in enumerate(loader, start=start_batch):
        x, y = batch[0], batch[1]
        last_batch = batch_idx == last_idx
        data_time_m.update(time.monotonic() - end)

        if profile_n and batch_idx == profile_start and not profiling:
            start_trace(os.path.join(output_dir, "profile"))
            profiling = True

        if chaos is not None and chaos.fires("nanbatch", num_updates):
            # poisoned input → non-finite loss AND grads inside the jitted
            # step (same shape/dtype: no recompile) — exercises the
            # device-side skip and, in a burst, the rewind path
            _logger.warning("chaos: poisoning batch at update %d",
                            num_updates)
            # keep the poisoned batch on the ORIGINAL sharding: the jitted
            # step pins its in_shardings, and an eager full_like lands
            # wherever XLA likes
            x = jax.device_put(jnp.full_like(x, np.nan),
                               getattr(x, "sharding", None)) \
                if hasattr(x, "sharding") else jnp.full_like(x, np.nan)

        step_rng = jax.random.fold_in(rng, num_updates)
        if first_step and step_exec is None:
            step_exec = _compile_aligned(train_step, "train_step",
                                         state, x, y, step_rng)
        first_step = False
        t_call = time.monotonic()
        with StepTraceAnnotation("dfd.train.step", step_num=num_updates):
            state, metrics = (step_exec or train_step)(state, x, y, step_rng)
        dispatch_s = time.monotonic() - t_call

        if profiling and (batch_idx + 1 >= profile_start + profile_n
                          or last_batch):
            jax.block_until_ready(metrics["loss"])
            jax.profiler.stop_trace()
            profiling = False
            _logger.info("Profiler trace written to %s",
                         os.path.join(output_dir, "profile"))

        bs = x.shape[0]     # GLOBAL batch: the loader assembles the global
        # sharded array even multi-host (parallel/sharding.py:69-80)
        pending.append((metrics, bs, num_updates))
        num_updates += 1

        if last_batch or batch_idx % cfg.log_interval == 0:
            _drain()
        now = time.monotonic()
        batch_time_m.update(now - end)
        if telemetry is not None:
            # host floats the loop already holds — no device access
            was, loader_s = loader_s, _loader_seconds(lstats)
            telemetry.on_step(
                bs, data_time_m.val, batch_time_m.val,
                tokens=x.size if seq_len else 0,
                update=num_updates - 1, batch=batch_idx, period=now - t_row,
                phases=dict(
                    {k: v - was[k] for k, v in loader_s.items()},
                    dispatch=dispatch_s, drain=drain_row, save=save_row))
            t_row, drain_row, save_row = now, 0.0, 0.0
        if profiler is not None:
            # cheap flag check when idle; manages an active trace window
            profiler.on_step(num_updates, metrics.get("loss"))

        if last_batch or batch_idx % cfg.log_interval == 0:
            lr = get_learning_rate(state) or 0.0
            ets_time = batch_time_m.avg * (num_batches - batch_idx) / 60
            _logger.info(
                "Train:%d [%4d/%d] "
                "Loss:%.5f(%.5f) Prec@1:%7.4f(%7.4f) "
                "Time:%.3f(%.3f)s/batch %.5f(%.5f)s/image "
                "LR:%.3e Data:%.3f(%.3f)s/batch ETS:%.3fmin",
                epoch, batch_idx, num_batches,
                losses_m.val, losses_m.avg, prec1_m.val, prec1_m.avg,
                batch_time_m.val, batch_time_m.avg,
                batch_time_m.val / max(bs // world_size, 1),
                batch_time_m.avg / max(bs // world_size, 1),
                lr, data_time_m.val, data_time_m.avg, ets_time)
            if telemetry is not None:
                # one record per drain cadence: breakdown + JSONL
                telemetry.on_drain(
                    epoch=epoch, batch_idx=batch_idx,
                    num_updates=num_updates, loss=losses_m.avg,
                    prec1=prec1_m.avg, lr=lr, drain_wait_s=drain_wait_acc,
                    nonfinite_steps=drain_bad_acc)
                drain_wait_acc, drain_bad_acc = 0.0, 0
            if profiler is not None:
                profiler.poll()         # PROFILE trigger file: 1 stat/drain
            if cfg.save_images and output_dir and jax.process_index() == 0:
                xd = x
                if getattr(cfg, "stem_s2d", False):
                    # the loader prologue pixel-shuffled the batch for the
                    # s2d stem — un-shuffle so the dump shows real frames,
                    # not 2x2 subpixel phases
                    from ..ops.conv import depth_to_space
                    xd = depth_to_space(np.asarray(x, np.float32))
                save_image_batch(
                    xd, os.path.join(output_dir,
                                     f"train-batch-{batch_idx}.jpg"),
                    img_num=max(1, cfg.resolved_in_chans // 3))

        if cfg.recovery_interval and (
                last_batch or (batch_idx + 1) % cfg.recovery_interval == 0):
            _snapshot(batch_idx)                            # ref :686-689

        if chaos is not None and saver is not None and \
                chaos.fires("truncate_ckpt", num_updates):
            _chaos_truncate(saver.curr_recovery_file or saver.find_recovery())

        if lr_scheduler is not None:
            # no stock schedule consumes a per-update metric (plateau is
            # epoch-granular and fed the FRESH eval metric by the runner);
            # one that declares it wants one must get a fresh value, not
            # the log-interval-stale buffered average
            metric = None
            if getattr(lr_scheduler, "wants_update_metric", False):
                _drain()
                metric = losses_m.avg
            new_lr = lr_scheduler.step_update(num_updates=num_updates,
                                              metric=metric)
            if new_lr is not None and new_lr != lr:
                state = set_learning_rate(state, new_lr)

        if resilience is not None:
            resilience.heartbeat(f"epoch {epoch} batch {batch_idx}/"
                                 f"{num_batches} update {num_updates}")
            if chaos is not None and chaos.fires("sigterm", num_updates):
                _logger.warning("chaos: delivering SIGTERM to self at "
                                "update %d", num_updates)
                os.kill(os.getpid(), signal.SIGTERM)
            stop = resilience.stop_requested
            rewind = False
            if jax.process_count() > 1:
                # host-local verdicts (each host gets its own SIGTERM at
                # its own boundary; a guard streak could in principle
                # diverge) cannot drive lockstep actions one-sidedly.
                # Agree IN-BAND at the drain cadence — a pure function of
                # loop indices every host walks identically, so the
                # collective cannot one-side — then every host stops /
                # rewinds at the SAME boundary, which is what makes the
                # snapshot below and the collective restore safe.
                if last_batch or batch_idx % cfg.log_interval == 0:
                    stop, rewind = resilience.sync_verdicts()
                else:
                    stop = rewind = False   # defer to the next boundary
            if rewind:
                raise RewindRequested(resilience.guard.rewind_reason
                                      or "coordinated rewind")
            if stop:
                # stop at THIS step boundary: drain buffered metrics (a
                # host sync, so the state below is the post-step state),
                # write a SYNCHRONOUS recovery snapshot carrying the exact
                # loop position, and unwind — the runner exits with the
                # preemption code so a wrapper can relaunch --auto-resume.
                # Multi-host both save paths (rank-0 gather / collective
                # Orbax write) are lockstep ops — safe exactly because the
                # agreement above put every host here together.
                _drain()
                _snapshot(batch_idx, sync=True)
                raise Preempted(epoch, batch_idx, resilience.stop_signum)
        end = time.monotonic()

    return state, OrderedDict([("loss", losses_m.avg),
                               ("prec1", prec1_m.avg),
                               ("learning_rate", lr),
                               ("nonfinite", nonfinite_total)])


def _loader_seconds(stats) -> dict:
    """The loader's four waits so far (``data/loader.py:LoaderStats``), by
    their ``STEP_PHASES`` names; none for a loader that keeps no stats."""
    if stats is None:
        return {}
    return dict(host_wait=stats.host_wait_s, stage=stats.stage_s,
                h2d_block=stats.h2d_block_s,
                prologue_block=stats.prologue_block_s)


@functools.partial(annotate_function, name="dfd.train.recovery_save")
def _save_recovery(saver, state, meta, epoch: int, batch_idx: int,
                   num_updates: int, sync: bool = False) -> None:
    """In-epoch recovery snapshot with exact loop position in the meta.

    EVERY rank calls this. Collective (sharded) saver: the save itself is
    the cross-host path — all ranks drive it, no gather. Otherwise every
    rank enters the gather and only rank 0 (the one holding a saver)
    writes.  ``num_updates`` is the update count AFTER ``batch_idx``
    completed, i.e. the value to continue with at ``batch_idx + 1``.
    """
    meta = dict(meta or {}, num_updates=num_updates)
    if saver is not None and saver.collective:
        saver.save_recovery(state, meta, epoch, batch_idx=batch_idx)
    else:
        from .checkpoint import replicate_for_save
        save_state = replicate_for_save(state) \
            if jax.process_count() > 1 else state
        if saver is not None:
            saver.save_recovery(save_state, meta, epoch,
                                batch_idx=batch_idx, sync=sync)


def _chaos_truncate(path: str) -> None:
    """Chaos point: tear the newest recovery file in half, as a crash mid
    ``os.replace``-less write would (exercises the CheckpointCorrupt
    fallback chain in --auto-resume)."""
    from .checkpoint import wait_pending_saves
    wait_pending_saves()            # the async write must have landed
    if not path or not os.path.exists(path):
        return
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(max(size // 2, 1))
    _logger.warning("chaos: truncated checkpoint %s (%d -> %d bytes)",
                    path, size, max(size // 2, 1))


def validate(eval_step: Callable, state: TrainState, loader, cfg,
             log_suffix: str = "", resilience=None
             ) -> "OrderedDict[str, float]":
    """Full-dataset eval (reference validate, train.py:703-767), exact thanks
    to the validity mask on padded batches.  ``resilience`` keeps the stall
    watchdog fed during eval (eval batches are its step completions here)."""
    batch_time_m = AverageMeter()
    losses_m, prec1_m = AverageMeter(), AverageMeter()
    all_scores, all_labels, all_valid = [], [], []
    end = time.time()
    num_batches = len(loader)
    last_idx = num_batches - 1
    log_name = "Test" + log_suffix
    eval_exec = None
    for batch_idx, batch in enumerate(loader):
        x, y = batch[0], batch[1]
        valid = batch[2] if len(batch) > 2 else None
        if batch_idx == 0:
            eval_exec = _compile_aligned(eval_step, "eval_step",
                                         state, x, y, valid)
        metrics = (eval_exec or eval_step)(state, x, y, valid)
        n = float(metrics["count"])
        if n > 0:
            losses_m.update(float(metrics["loss"]), n)
            prec1_m.update(float(metrics["prec1"]), n)
        logits = metrics.get("logits")
        if logits is not None and logits.shape[-1] == 2:
            # P(real): labels are 0=fake / 1=real, so AUC ranks real above
            # fake (the released-checkpoint quality gate).
            # Accumulate only this process's rows here; the cross-process
            # gather happens ONCE after the loop (a per-batch allgather
            # would force a host sync every eval batch).
            scores = _host_local_rows(jax.nn.softmax(logits, axis=-1)[:, 1])
            all_scores.append(scores.astype(np.float32).reshape(-1))
            all_labels.append(_host_local_rows(y).reshape(-1))
            all_valid.append(np.ones(len(scores), np.float32) if valid is None
                             else _host_local_rows(valid)
                             .astype(np.float32).reshape(-1))
        batch_time_m.update(time.time() - end)
        if resilience is not None:
            resilience.heartbeat(f"eval batch {batch_idx}/{num_batches}")
        if batch_idx == last_idx or batch_idx % cfg.log_interval == 0:
            _logger.info(
                "%s: [%4d/%d] Time:%.3f(%.3f) "
                "Loss:%.4f(%.4f) Prec@1:%7.4f(%7.4f)",
                log_name, batch_idx, num_batches,
                batch_time_m.val, batch_time_m.avg,
                losses_m.val, losses_m.avg, prec1_m.val, prec1_m.avg)
        end = time.time()
    out = OrderedDict([("loss", losses_m.avg), ("prec1", prec1_m.avg)])
    if all_scores:
        scores = np.concatenate(all_scores)
        labels = np.concatenate(all_labels)
        valids = np.concatenate(all_valid)
        if jax.process_count() > 1:
            # one gather for the whole epoch; AUC is a rank statistic, so
            # cross-process row order is irrelevant
            from jax.experimental import multihost_utils
            scores, labels, valids = multihost_utils.process_allgather(
                (scores, labels, valids), tiled=True)
        out["auc"] = float(auc(scores, labels, valids))
        _logger.info("%s: AUC %.5f", log_name, out["auc"])
    return out


def _compile_aligned(fn, tag: str, *args):
    """Multi-process: AOT-compile a step, barrier, return the executable.

    Cross-process collective-context creation (gloo on CPU; similar
    rendezvous elsewhere) has a short (~30 s) deadline that fires during
    the FIRST execution if another rank is still jit-compiling — and jit
    compilation is host-synchronous, so per-rank compile skew (minutes on
    contended hosts) lands entirely between one rank's enqueue and the
    other's.  Compiling ahead-of-time and meeting at a barrier puts every
    rank's first execution within milliseconds; the returned executable is
    then used for EVERY step (batch shapes are static), so nothing
    compiles twice.  Returns None (caller keeps the plain jit path) for
    single-process runs or if AOT lowering fails.
    """
    if jax.process_count() <= 1 or not hasattr(fn, "lower"):
        return None
    # memoize on the jitted-function object (built once per run): later
    # epochs / validate calls reuse the executable with no recompile and
    # no extra barrier
    exe = getattr(fn, "_aligned_exec", None)
    if exe is not None:
        return exe
    try:
        exe = fn.lower(*args).compile()
    except Exception as e:  # noqa: BLE001 — alignment must never kill a run
        _logger.warning("%s pre-compile failed (%r); continuing on the "
                        "plain jit path", tag, e)
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(f"{tag}_compiled")
    if exe is not None:
        try:
            fn._aligned_exec = exe
        except AttributeError:
            pass                       # non-writable callables: recompile
    return exe


def _host_local_rows(a) -> np.ndarray:
    """This process's rows of an axis-0-sharded array, as numpy.

    Single-process (and plain numpy input): the whole array.  Multi-process:
    the addressable shards, deduplicated by row range (a replicated array has
    one full copy per local device) and stitched in row order.
    """
    if isinstance(a, np.ndarray) or jax.process_count() == 1:
        return np.asarray(a)
    uniq = {}
    for s in a.addressable_shards:
        idx = s.index[0] if s.index else slice(None)
        uniq.setdefault((idx.start, idx.stop), s)
    shards = [uniq[k] for k in sorted(uniq, key=lambda t: t[0] or 0)]
    return np.concatenate([np.asarray(s.data) for s in shards], axis=0)
