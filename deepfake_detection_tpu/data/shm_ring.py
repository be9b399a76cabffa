"""Process-parallel host loader: a shared-memory ring of batch slabs.

The thread-pool :class:`~deepfake_detection_tpu.data.loader.HostLoader`
parallelizes decode inside ONE process — fine while every hot stage releases
the GIL, but the Python glue between stages (PIL objects, numpy views, the
collate ``np.stack``) serializes, and its share of the clip budget caps
scaling well below the core count.  This module is the torch-DataLoader
equivalent for the TPU port: N *spawned* worker processes (no GIL sharing,
no fork-inherited thread pools) decode + transform samples and write the
resulting uint8 NHWC bytes **directly into a preallocated
``multiprocessing.shared_memory`` ring of batch slabs** at their sample's
slot offset — collate is zero-copy, the batch simply *appears* in the slab
as its last worker finishes, and the consumer hands the slab view straight
to ``jax.device_put`` (no pickle IPC of image bytes anywhere).

Determinism: a sample's content is a pure function of ``(seed, epoch,
index)`` — workers derive the identical per-sample RNG the thread loader
uses, so ``thread`` and ``shm`` backends produce bit-identical batches for
any worker count (tested in ``tests/test_shm_loader.py``).  That purity is
also what makes crash recovery trivial: re-executing a lost task rewrites
the same bytes, so recovery is idempotent by construction.

Robustness:

* **Backpressure** — at most ``ring_depth`` batches are ever in flight; the
  task queue is bounded by ``ring_depth * batch_size`` sample tasks and a
  slab slot is only re-dispatched after the consumer has moved two batches
  past it (see the reuse contract below).
* **Worker crashes** — each worker publishes its current ``(batch, slot)``
  task in a shared cell before touching the sample; the consumer polls
  ``exitcode`` while collecting, respawns dead workers, and re-dispatches
  exactly the one task a dead worker can have lost.
* **Stalls** — workers heartbeat a shared timestamp per task; a worker that
  is alive but silent past ``heartbeat_timeout`` while holding a task is
  terminated and handled like a crash.
* **Shutdown** — ``close()`` (also wired to a ``weakref.finalize``) stops
  workers, drains queues, and unlinks the shm segment; abandoned iterators
  are quiesced with a generation counter so stale tasks can never write
  into a recycled slab.

Slab-reuse contract: a yielded image batch is a **view into the ring** and
stays valid until TWO further batches have been requested from the
iterator.  ``DeviceLoader`` enforces this by blocking on the previous
batch's prologue output before pulling the batch that would recycle the
slot (jax CPU ``device_put`` zero-copies aligned host buffers, so this is
load-bearing, not just belt-and-braces).  Consumers that hold host batches
longer must copy.  Targets and valid masks are tiny and always copied.

No jax imports here: spawned workers import only numpy + the dataset's own
dependencies (PIL, the ctypes native decoder), keeping worker startup and
memory footprint small.
"""

from __future__ import annotations

import logging
import multiprocessing as mp
import os
import queue as queue_mod
import time
import weakref
from multiprocessing import shared_memory
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from .samplers import epoch_batches

_logger = logging.getLogger(__name__)

__all__ = ["ShmRing", "ShmRingLoader"]


def _attach_untracked(name: str) -> shared_memory.SharedMemory:
    """Attach to an existing segment WITHOUT registering it with the
    resource tracker: attachers registering the creator's segment makes
    the (process-tree-shared) tracker unlink it when any worker exits
    (bpo-38119), yanking the ring out from under the survivors.  Python
    3.13 grew ``track=False`` for exactly this; on older interpreters the
    registration hook is swapped out for the duration of the attach
    (single-threaded worker startup, so the swap cannot race)."""
    try:
        return shared_memory.SharedMemory(name=name, track=False)
    except TypeError:  # pre-3.13: no track kwarg
        pass
    from multiprocessing import resource_tracker
    orig = resource_tracker.register
    resource_tracker.register = lambda *a, **k: None
    try:
        return shared_memory.SharedMemory(name=name)
    finally:
        resource_tracker.register = orig


class ShmRing:
    """``depth`` batch slabs in one shared-memory segment.

    Layout: ``(depth, rows, H, W, C)`` uint8 image slabs followed (64-byte
    aligned) by ``(depth, batch)`` int64 target slabs.  ``rows`` is
    ``batch * num_splits`` — AugMix multi-view samples land split-major,
    exactly the layout ``fast_collate`` produces on the thread path.
    """

    def __init__(self, depth: int, rows: int, img_shape: Sequence[int],
                 batch: int, name: Optional[str] = None,
                 create: bool = False):
        self.depth = int(depth)
        self.rows = int(rows)
        self.img_shape = tuple(int(d) for d in img_shape)
        self.batch = int(batch)
        img_bytes = self.depth * self.rows * int(np.prod(self.img_shape))
        self._tgt_off = -(-img_bytes // 64) * 64
        total = self._tgt_off + self.depth * self.batch * 8
        if create:
            self.shm = shared_memory.SharedMemory(create=True, size=total)
        else:
            self.shm = _attach_untracked(name)
        self.images = np.ndarray((self.depth, self.rows) + self.img_shape,
                                 np.uint8, buffer=self.shm.buf)
        self.targets = np.ndarray((self.depth, self.batch), np.int64,
                                  buffer=self.shm.buf, offset=self._tgt_off)

    @property
    def name(self) -> str:
        return self.shm.name

    def close(self, unlink: bool = False) -> None:
        self.images = None
        self.targets = None
        try:
            self.shm.close()
        except BufferError:
            # a consumer still holds a yielded slab view; the mapping is
            # freed when the last view dies / the process exits — unlink
            # below still removes the name so nothing leaks system-wide
            pass
        if unlink:
            try:
                self.shm.unlink()
            except FileNotFoundError:
                pass


# ---------------------------------------------------------------------------
# Worker process
# ---------------------------------------------------------------------------

def _owner_token(gen: int, bi: int) -> int:
    """One int64 identifying which (iteration, batch) owns a ring slot."""
    return (int(gen) << 32) | (int(bi) & 0xFFFFFFFF)


def _worker_main(wid: int, dataset: Any, seed: int, shm_name: str,
                 depth: int, rows: int, img_shape: Tuple[int, ...],
                 batch: int, task_q, done_q, stop_ev, hb, cur, gen, owner,
                 native_threads: int) -> None:
    """One decode worker: pull ``(slot, j, index, epoch, bi, gen)`` sample
    tasks, write the transformed uint8 sample at its slot offset, ack on
    ``done_q``.  Errors are reported per-sample, not fatal — the consumer
    decides.  Protocol order matters for crash recovery: the current-task
    cell is set BEFORE any work and cleared only AFTER the done ack, so the
    consumer can always reconstruct what a dead worker may have lost.
    Before touching a slab the worker verifies it still OWNS the slot
    (``owner[slot]`` carries the (gen, bi) token the consumer wrote at
    dispatch): a stale task — from an abandoned iteration, or a duplicate
    from a lost-ack re-dispatch executed after its batch completed — must
    never write into a recycled slab."""
    try:
        from . import native as _native
        _native.set_default_pool_threads(native_threads)
    except Exception:  # pragma: no cover - native module is optional
        pass
    chaos = None
    if wid == 0 and os.environ.get("DFD_CHAOS"):
        # env-gated fault injection (worker 0 only, deterministic): die
        # after the Nth completed task so the consumer's crash-recovery
        # path (respawn + re-dispatch) is driven by a REAL dead process
        from ..chaos import chaos_from_env
        chaos = chaos_from_env()
        if "kill_shm_worker" not in chaos.points:
            chaos = None
    tasks_done = 0
    ring = ShmRing(depth, rows, img_shape, batch, name=shm_name)
    base = 3 * wid
    last_epoch: Optional[int] = None
    try:
        while True:
            try:
                task = task_q.get(timeout=0.5)
            except queue_mod.Empty:
                hb[wid] = time.monotonic()
                if stop_ev.is_set():
                    break
                continue
            if task is None:
                break
            if chaos is not None and chaos.fires("kill_shm_worker",
                                                 tasks_done):
                os._exit(113)       # hard death: no ack, no cleanup
            tasks_done += 1
            slot, j, index, epoch, bi, task_gen = task
            cur[base + 1] = bi
            cur[base + 2] = j
            cur[base] = 1
            hb[wid] = time.monotonic()
            token = _owner_token(task_gen, bi)
            if task_gen != gen.value or owner[slot] != token:
                cur[base] = 0
                continue
            err = None
            try:
                if epoch != last_epoch:
                    if hasattr(dataset, "set_epoch"):
                        dataset.set_epoch(epoch)
                    last_epoch = epoch
                rng = np.random.default_rng(
                    np.random.SeedSequence([seed, epoch, int(index)]))
                img, target = dataset.__getitem__(int(index), rng=rng)
                arr = np.asarray(img, dtype=np.uint8)
                if owner[slot] == token:
                    # authoritative pre-write check: the slot may have been
                    # recycled while this (stale/duplicate) task decoded
                    if arr.ndim == 4:    # (S, H, W, C) AugMix views →
                        for s in range(arr.shape[0]):   # split-major rows
                            ring.images[slot, s * batch + j] = arr[s]
                    else:
                        ring.images[slot, j] = arr
                    ring.targets[slot, j] = int(target)
            except Exception as e:      # report, keep serving; interrupts
                err = f"{type(e).__name__}: {e}"   # (Ctrl-C → SIGINT to the
                # process group) must NOT become a per-sample error that
                # beats the consumer's own KeyboardInterrupt to the punch —
                # they propagate, the worker dies, crash handling applies
            done_q.put((task_gen, bi, j, err))
            cur[base] = 0
    finally:
        ring.close()


# ---------------------------------------------------------------------------
# Consumer
# ---------------------------------------------------------------------------

def _shutdown(stop_ev, workers: List, task_q, done_q,
              ring: Optional[ShmRing]) -> None:
    """Idempotent teardown shared by close() and the weakref finalizer.
    Must not reference the loader object (finalizer callback)."""
    try:
        stop_ev.set()
    except Exception:
        pass
    for p in workers:
        try:
            task_q.put_nowait(None)
        except Exception:
            break
    deadline = time.monotonic() + 5.0
    for p in workers:
        p.join(timeout=max(0.1, deadline - time.monotonic()))
    for p in workers:
        if p.is_alive():
            p.terminate()
            p.join(timeout=2.0)
    for q in (task_q, done_q):
        try:
            q.cancel_join_thread()
            q.close()
        except Exception:
            pass
    if ring is not None:
        ring.close(unlink=True)


class ShmRingLoader:
    """Drop-in replacement for :class:`HostLoader` backed by worker
    *processes* and a shared-memory slab ring (module docstring has the
    full design).  Same contract: yields ``(images_uint8, targets)``
    numpy batches (plus a valid mask for masked eval), every batch a pure
    function of ``(seed, epoch, batch_index)``.
    """

    def __init__(self, dataset, sampler, batch_size: int, seed: int = 42,
                 num_workers: int = 4, ring_depth: int = 4,
                 collate_mixup: Optional[Any] = None,
                 valid_mask: bool = False,
                 heartbeat_timeout: float = 120.0):
        self.dataset = dataset
        self.sampler = sampler
        self.batch_size = batch_size
        self.seed = seed
        self.num_workers = max(1, int(num_workers))
        self.ring_depth = max(3, int(ring_depth))
        self.collate_mixup = collate_mixup
        self.valid_mask = valid_mask
        self.heartbeat_timeout = float(heartbeat_timeout)
        self.epoch = 0
        # mid-epoch resume: first yielded batch of the next iteration
        # (absolute indices are kept for slot tokens and per-batch RNG);
        # reset by set_epoch — see HostLoader.start_batch
        self.start_batch = 0
        self.respawn_count = 0          # lifetime total: observability/tests
        self._iter_respawns = 0         # windowed: crash-loop abort guard
        self._slow_tasks: Set[Tuple[int, int]] = set()  # kill-once ledger
        # telemetry counters (obs/telemetry.py loader_collector): lifetime
        # totals, single-writer (the consumer thread), torn-proof reads
        self.stall_sweeps = 0           # lost-ack re-dispatch sweeps fired
        self.inflight_batches = 0       # dispatched, not yet yielded (ring
        # occupancy = inflight_batches / ring_depth)

        self._ctx = mp.get_context("spawn")
        self._ring: Optional[ShmRing] = None
        self._workers: List[Any] = []
        self._finalizer: Optional[weakref.finalize] = None
        self._dirty = False             # iterator abandoned mid-epoch
        self._splits = 1
        self._img_shape: Tuple[int, ...] = ()
        self._rows = 0

    # -- HostLoader interface parity ------------------------------------
    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch
        self.start_batch = 0
        self.sampler.set_epoch(epoch)
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.sampler) // self.batch_size

    # -- lifecycle ------------------------------------------------------
    def _ensure_started(self) -> None:
        if self._ring is not None:
            return
        probe_index = next(iter(self.sampler), None)
        if probe_index is None:
            raise ValueError("sampler yields no indices")
        # one probe decode in the parent fixes the slab geometry; workers
        # recompute the sample, so the probe costs one clip, not parity
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.seed, self.epoch, int(probe_index)]))
        img, _ = self.dataset.__getitem__(int(probe_index), rng=rng)
        arr = np.asarray(img, dtype=np.uint8)
        if arr.ndim == 4:
            self._splits, self._img_shape = int(arr.shape[0]), arr.shape[1:]
        elif arr.ndim == 3:
            self._splits, self._img_shape = 1, arr.shape
        else:
            raise ValueError(f"sample must be (H, W, C) or (S, H, W, C), "
                             f"got shape {arr.shape}")
        self._rows = self._splits * self.batch_size
        self._ring = ShmRing(self.ring_depth, self._rows, self._img_shape,
                             self.batch_size, create=True)
        self._task_q = self._ctx.Queue()
        self._done_q = self._ctx.Queue()
        self._stop = self._ctx.Event()
        self._hb = self._ctx.Array("d", self.num_workers, lock=False)
        self._cur = self._ctx.Array("q", 3 * self.num_workers, lock=False)
        self._gen = self._ctx.Value("q", 0, lock=False)
        self._owner = self._ctx.Array("q", self.ring_depth, lock=False)
        # each worker's in-process native decode pool gets a slice of the
        # cores — N workers x 4 default threads would oversubscribe
        self._native_threads = max(
            1, min(4, (os.cpu_count() or 1) // self.num_workers))
        self._workers = [None] * self.num_workers
        for i in range(self.num_workers):
            self._spawn(i)
        self._finalizer = weakref.finalize(
            self, _shutdown, self._stop, self._workers, self._task_q,
            self._done_q, self._ring)

    def _spawn(self, i: int) -> None:
        self._hb[i] = time.monotonic()
        self._cur[3 * i] = 0
        p = self._ctx.Process(
            target=_worker_main,
            args=(i, self.dataset, self.seed, self._ring.name,
                  self.ring_depth, self._rows, self._img_shape,
                  self.batch_size, self._task_q, self._done_q, self._stop,
                  self._hb, self._cur, self._gen, self._owner,
                  self._native_threads),
            daemon=True, name=f"dfd-shm-worker-{i}")
        # chaos worker-kill is a TRANSIENT fault: the replacement worker
        # must not inherit the spec and die again in a loop (spawn-context
        # children snapshot os.environ at start)
        chaos_env = os.environ.pop("DFD_CHAOS", None) \
            if self.respawn_count else None
        try:
            p.start()
        finally:
            if chaos_env is not None:
                os.environ["DFD_CHAOS"] = chaos_env
        self._workers[i] = p

    def close(self) -> None:
        """Stop workers, drain queues, unlink the shm segment.  Safe to
        call twice; also runs via weakref.finalize on GC/interpreter exit."""
        if self._finalizer is not None:
            self._finalizer()
        self._ring = None
        self._workers = []

    # -- iteration ------------------------------------------------------
    def _quiesce(self) -> None:
        """After an abandoned iteration: invalidate outstanding tasks (gen
        bump), drain them, and wait for in-flight writes to land so no
        stale worker can touch a slab the new epoch re-dispatches."""
        self._gen.value += 1
        while True:
            try:
                self._task_q.get_nowait()
            except (queue_mod.Empty, OSError):
                break
        deadline = time.monotonic() + 30.0
        while any(self._cur[3 * i] for i in range(self.num_workers)):
            for i, p in enumerate(self._workers):
                if p.exitcode is not None and self._cur[3 * i]:
                    self._cur[3 * i] = 0      # dead: can't clear its flag
                    self.respawn_count += 1
                    self._spawn(i)
            if time.monotonic() > deadline:
                # a straggler stuck in __getitem__ on a stale task that
                # already passed its gen check would eventually write into
                # a slab the next epoch re-dispatches — kill it rather
                # than risk a silent corrupt batch
                for i, p in enumerate(self._workers):
                    if self._cur[3 * i]:
                        _logger.warning("shm worker %d still busy after "
                                        "quiesce deadline; terminating", i)
                        p.terminate()
                        p.join(timeout=5.0)
                        self.respawn_count += 1
                        self._spawn(i)
                break
            time.sleep(0.01)
        while True:
            try:
                self._done_q.get_nowait()
            except (queue_mod.Empty, OSError):
                break
        self._dirty = False

    def _check_workers(self, done: Dict[int, Set[int]],
                       batches: List[List[int]], epoch: int,
                       gen: int) -> None:
        now = time.monotonic()
        for i in range(self.num_workers):
            p = self._workers[i]
            dead = p.exitcode is not None
            base = 3 * i
            if not dead and self._cur[base] and \
                    now - self._hb[i] > self.heartbeat_timeout:
                tkey = (int(self._cur[base + 1]), int(self._cur[base + 2]))
                if tkey in self._slow_tasks:
                    # this exact task already stalled a worker once: the
                    # sample is deterministic, so a re-kill loop would
                    # abort healthy-but-slow data (cold storage, a huge
                    # clip) — let the re-execution run to completion
                    continue
                self._slow_tasks.add(tkey)
                _logger.warning(
                    "shm worker %d silent for %.0fs on a task; killing",
                    i, now - self._hb[i])
                p.terminate()
                p.join(timeout=5.0)
                dead = True
            if not dead:
                continue
            flag, bi, j = (self._cur[base], int(self._cur[base + 1]),
                           int(self._cur[base + 2]))
            self.respawn_count += 1
            self._iter_respawns += 1
            # windowed (reset each epoch): isolated, fully-recovered
            # crashes over a long run must not accumulate into an abort —
            # only an actual crash loop within one epoch should
            if self._iter_respawns > 3 * self.num_workers:
                raise RuntimeError(
                    "shm loader: workers keep dying "
                    f"({self._iter_respawns} respawns this epoch); "
                    "giving up")
            _logger.warning("shm worker %d died (exitcode %s); respawning",
                            i, p.exitcode)
            self._spawn(i)
            # the dead worker held at most ONE task; everything else is
            # still queued or already acked.  Re-dispatch it unless its
            # ack made it out before the crash.  Deterministic samples
            # make a duplicate execution write identical bytes.
            if flag and bi in done and j < len(batches[bi]) \
                    and j not in done[bi]:
                self._task_q.put((bi % self.ring_depth, j,
                                  int(batches[bi][j]), epoch, bi, gen))

    def _collect(self, bi: int, done: Dict[int, Set[int]],
                 batches: List[List[int]], epoch: int, gen: int) -> None:
        need = len(batches[bi])
        last_progress = time.monotonic()
        sweeps = 0
        while len(done.get(bi, ())) < need:
            try:
                g, dbi, j, err = self._done_q.get(timeout=0.2)
            except queue_mod.Empty:
                self._check_workers(done, batches, epoch, gen)
                # lost-ack net: a worker that died between completing a
                # sample and its ack actually reaching the pipe (the ack
                # rides the dying process's queue feeder thread) leaves
                # done[bi] short with nothing in flight.  When the batch
                # stalls, re-dispatch its unacked samples that no live
                # worker is holding — duplicates are harmless (the worker-
                # side owner check blocks any late write into a recycled
                # slab, and identical bytes land when the slot is current).
                now = time.monotonic()
                if now - last_progress > max(5.0, self.heartbeat_timeout / 8):
                    sweeps += 1
                    self.stall_sweeps += 1
                    if sweeps > 20:
                        raise RuntimeError(
                            f"shm loader: batch {bi} stalled "
                            f"({len(done.get(bi, ()))}/{need} samples after "
                            f"{sweeps} re-dispatch sweeps)")
                    busy = {(int(self._cur[3 * i + 1]),
                             int(self._cur[3 * i + 2]))
                            for i in range(self.num_workers)
                            if self._cur[3 * i]}
                    for j2 in range(need):
                        if j2 not in done.get(bi, ()) and \
                                (bi, j2) not in busy:
                            self._task_q.put(
                                (bi % self.ring_depth, j2,
                                 int(batches[bi][j2]), epoch, bi, gen))
                    last_progress = now
                continue
            last_progress = time.monotonic()
            if g != gen:
                continue
            if err is not None:
                raise RuntimeError(
                    f"shm worker failed on sample {j} of batch {dbi}: {err}")
            done.setdefault(dbi, set()).add(j)

    def __iter__(self):
        batches, vms = epoch_batches(self.sampler, self.batch_size,
                                     self.valid_mask)
        start = self.start_batch
        if not batches or start >= len(batches):
            return
        self._ensure_started()
        if self._dirty:
            self._quiesce()
        self._gen.value += 1
        gen = int(self._gen.value)
        self._dirty = True
        self._iter_respawns = 0
        self._slow_tasks.clear()
        epoch = self.epoch
        D = self.ring_depth
        nb = len(batches)
        done: Dict[int, Set[int]] = {}

        def dispatch(bi: int) -> None:
            done.setdefault(bi, set())
            slot = bi % D
            # recycling gate: a worker can still be mid-write on this slot
            # under its PREVIOUS batch (a stale duplicate from a lost-ack
            # sweep, or an ack processed before the worker cleared its
            # cell).  Waiting for those published tasks to finish makes
            # the owner re-claim mutually exclusive with in-flight writes;
            # the worker-side pre-write token check covers the residual
            # window of a claim that has not published its cell yet.
            deadline = time.monotonic() + 10.0
            while any(self._cur[3 * i]
                      and int(self._cur[3 * i + 1]) != bi
                      and int(self._cur[3 * i + 1]) % D == slot
                      for i in range(self.num_workers)):
                if time.monotonic() > deadline:
                    _logger.warning("slot %d recycle gate timed out", slot)
                    break
                time.sleep(0.002)
            # claim the slot for (gen, bi) BEFORE its tasks exist: workers
            # verify this token right before any slab write
            self._owner[slot] = _owner_token(gen, bi)
            for j, idx in enumerate(batches[bi]):
                self._task_q.put((slot, j, int(idx), epoch, bi, gen))
            self.inflight_batches = len(done)

        for bi in range(start, min(start + D, nb)):
            dispatch(bi)
        for bi in range(start, nb):
            # slot of batch bi-2 is free by contract (the caller has
            # requested two batches past it) → refill the ring
            if bi >= start + 2 and bi - 2 + D < nb:
                dispatch(bi - 2 + D)
            self._collect(bi, done, batches, epoch, gen)
            images = self._ring.images[bi % D]
            targets = self._ring.targets[bi % D].copy()
            if self._splits > 1:
                targets = np.tile(targets, self._splits)
            if self.collate_mixup is not None:
                mrng = np.random.default_rng(np.random.SeedSequence(
                    [self.seed, epoch, bi, 0x77]))
                images, targets = self.collate_mixup(images, targets, mrng)
            done.pop(bi, None)
            self.inflight_batches = len(done)
            if vms is not None:
                yield images, targets, np.asarray(vms[bi])
            else:
                yield images, targets
        self._dirty = False
