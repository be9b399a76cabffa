"""Batch loading: host pipeline + device prologue.

Re-design of ``/root/reference/dfd/timm/data/loader.py``:

* ``fast_collate`` (:12-46) → :func:`fast_collate` — numpy uint8 stacking,
  NHWC.
* worker-process decode + transform → :class:`HostLoader` — a thread pool
  (PIL/numpy release the GIL for decode/resize) with a bounded prefetch
  queue; per-sample RNG derived from ``(seed, epoch, index)`` so output is
  identical for any worker count.
* ``PrefetchLoader_v3`` (:213-289 — CUDA-stream double buffering, fp16 cast,
  mean/std tiled ×img_num, GPU RandomErasing) → :class:`DeviceLoader` — a
  jitted prologue (uint8 → compute dtype, normalize, RandomErasing per frame
  slice) dispatched asynchronously; JAX's async dispatch + donated buffers
  replace the explicit CUDA stream dance.
* ``create_deepfake_loader_v3`` (:724-830) → :func:`create_deepfake_loader_v3`
  with the same knob surface.

Normalization parity: mean/std are ×255 (uint8 domain) tiled to all
``3*img_num`` channels (loader.py:228-229); casting happens *on device*, so
host→TPU transfers stay uint8 — 4× less PCIe/DMA traffic than shipping
floats.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
import logging
from typing import Any, Callable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

_logger = logging.getLogger(__name__)

from .constants import IMAGENET_DEFAULT_MEAN, IMAGENET_DEFAULT_STD
from .mixup import FastCollateMixup
from .random_erasing import RandomErasing
from .samplers import (OrderedShardedSampler, ShardedTrainSampler,
                       epoch_batches)
from .transforms_factory import (transforms_deepfake_eval_v3,
                                 transforms_deepfake_train_v3)

__all__ = ["fast_collate", "HostLoader", "DeviceLoader", "LoaderStats",
           "HostLoaderStats", "create_loader", "create_deepfake_loader_v3",
           "create_token_loader"]

LOADER_BACKENDS = ("thread", "shm")


class LoaderStats:
    """Monotonic DeviceLoader counters (obs/telemetry.py input gauges).

    ``time.monotonic`` deltas around what the loader ALREADY does for each
    batch — no new syncs, no locks (single writer: the consumer thread;
    telemetry reads are torn-proof float loads under the GIL).  Each timed
    region is also a ``dfd.input.*`` span on the profiler's clock.
    """

    __slots__ = ("batches", "host_wait_s", "stage_block_s", "h2d_block_s",
                 "prologue_block_s", "stage_s", "augment_elided")

    def __init__(self):
        self.batches = 0        # batches staged to device
        self.host_wait_s = 0.0  # blocked in next(host_loader) — input starved
        self.stage_block_s = 0.0  # blocked in the slab-recycle wait: the
        # sum of the two below
        self.h2d_block_s = 0.0  # ... on the batch's host-to-device copy.
        # The pipeline starving the chip only where the device is idle too
        # (device_idle_share.train): a copy queued behind a running step
        # waits here as well, and the loop would wait below instead
        self.prologue_block_s = 0.0  # ... then on its prologue, queued
        # behind the running step (the chip is the bottleneck)
        self.stage_s = 0.0      # in _stage: device_put + prologue dispatch
        self.augment_elided = 0  # host augment stages elided by
        # --augment-device (samples x stages moved into the prologue)


class HostLoaderStats:
    """Producer-side thread-backend counters (written by the producer
    thread; same single-writer torn-proof contract as LoaderStats)."""

    __slots__ = ("batches", "load_s", "collate_s", "mixup_s", "put_wait_s")

    def __init__(self):
        self.batches = 0        # batches collated
        self.load_s = 0.0       # pool.map over the batch: decode+transform
        self.collate_s = 0.0    # fast_collate: stack to one uint8 array
        self.mixup_s = 0.0      # the collate_mixup call (uint8 blend)
        self.put_wait_s = 0.0   # blocked on the full prefetch queue
        # (consumer slower than the pipeline — healthy backpressure)


def _loader_chaos():
    """Chaos injector for loader-side fault points, None in production
    (``DFD_CHAOS`` unset — the probe then costs one env read per epoch).
    Fresh per iteration: loader points key on the batch index within an
    epoch, unlike the trainer's run-global update counter."""
    if not os.environ.get("DFD_CHAOS"):
        return None
    from ..chaos import chaos_from_env
    return chaos_from_env()


def fast_collate(samples: Sequence[Tuple[np.ndarray, int]],
                 dtype=np.uint8) -> Tuple[np.ndarray, np.ndarray]:
    """Stack uint8 NHWC samples + int labels (reference :12-46).  Token
    rows (``dtype`` int32, data/tokens.py) stack the same way, their
    per-position targets into one int32 array.

    AugMix multi-view samples — ``(S, H, W, C)`` per sample — collate
    split-major: ``[view0 of all samples, view1 of all samples, ...]`` with
    labels tiled, the layout ``jsd_cross_entropy`` splits back apart
    (reference fast_collate tuple branch, loader.py:15-27).
    """
    images = np.stack([s[0] for s in samples]).astype(dtype, copy=False)
    if isinstance(samples[0][1], np.ndarray):
        return images, np.stack([s[1] for s in samples])
    targets = np.asarray([s[1] for s in samples], dtype=np.int64)
    if images.ndim == 5:                       # (B, S, H, W, C)
        b, s = images.shape[:2]
        images = np.transpose(images, (1, 0, 2, 3, 4)).reshape(
            b * s, *images.shape[2:])
        targets = np.tile(targets, s)
    return images, targets


class HostLoader:
    """Decode + transform + collate on host threads with prefetch.

    Yields ``(images_uint8 (B,H,W,C), targets)`` numpy batches (targets are
    int64, or float32 soft targets when ``collate_mixup`` is set).  A batch's
    content is a pure function of ``(seed, epoch, batch_index)``.
    """

    def __init__(self, dataset, sampler, batch_size: int, seed: int = 42,
                 num_workers: int = 8, prefetch_depth: int = 2,
                 collate_mixup: Optional[FastCollateMixup] = None,
                 valid_mask: bool = False):
        self.dataset = dataset
        self.sampler = sampler
        self.batch_size = batch_size
        self.seed = seed
        self.num_workers = max(1, num_workers)
        self.prefetch_depth = max(1, prefetch_depth)
        self.collate_mixup = collate_mixup
        self.valid_mask = valid_mask
        self.epoch = 0
        self.stats = HostLoaderStats()
        # what a sample is stacked as: uint8 images, or a token dataset's
        # int32 ids (data/tokens.py)
        self.sample_dtype = getattr(dataset, "sample_dtype", np.uint8)
        # mid-epoch resume: skip producing batches < start_batch while
        # keeping their ABSOLUTE indices for every per-batch RNG, so a
        # fast-forwarded epoch's remaining batches are bit-identical to an
        # uninterrupted one.  Reset by set_epoch (one epoch's worth).
        self.start_batch = 0

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch
        self.start_batch = 0
        self.sampler.set_epoch(epoch)
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.sampler) // self.batch_size

    def _load_one(self, index: int) -> Tuple[np.ndarray, int]:
        with TraceAnnotation("dfd.input.sample", index=int(index)):
            rng = np.random.default_rng(
                np.random.SeedSequence([self.seed, self.epoch, int(index)]))
            img, target = self.dataset.__getitem__(int(index), rng=rng)
            return np.asarray(img, dtype=self.sample_dtype), target

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        batches, vms = epoch_batches(self.sampler, self.batch_size,
                                     self.valid_mask)
        start = self.start_batch
        chaos = _loader_chaos()
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch_depth)
        stop = threading.Event()
        stats = self.stats

        def put(item, bi: int) -> bool:
            """Bounded put that keeps observing ``stop`` (an abandoned
            consumer otherwise deadlocks the producer on the full queue)."""
            t0 = time.monotonic()
            try:
                with TraceAnnotation("dfd.input.put_wait", batch=bi):
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=0.1)
                            return True
                        except queue.Full:
                            continue
                    return False
            finally:
                stats.put_wait_s += time.monotonic() - t0

        def produce():
            with ThreadPoolExecutor(self.num_workers) as pool:
                for bi, batch_idx in enumerate(batches):
                    if bi < start:
                        continue
                    if stop.is_set():
                        return
                    if chaos is not None and chaos.fires("stall_loader", bi):
                        # simulates a wedged data source: no batch reaches
                        # the train loop until the sleep (default 120 s)
                        # ends — long enough to trip any sane watchdog
                        _logger.warning("chaos: stalling loader %.0fs at "
                                        "batch %d",
                                        chaos.arg("stall_loader", 120.0), bi)
                        time.sleep(chaos.arg("stall_loader", 120.0))
                    # the three phases of a batch, each a span on the
                    # profiler's clock and a counter
                    t0 = time.monotonic()
                    with TraceAnnotation("dfd.input.load", batch=bi):
                        samples = list(pool.map(self._load_one, batch_idx))
                    t1 = time.monotonic()
                    with TraceAnnotation("dfd.input.collate", batch=bi):
                        images, targets = fast_collate(
                            samples, self.sample_dtype)
                    t2 = t3 = time.monotonic()
                    if self.collate_mixup is not None:
                        mrng = np.random.default_rng(np.random.SeedSequence(
                            [self.seed, self.epoch, bi, 0x77]))
                        with TraceAnnotation("dfd.input.mixup", batch=bi):
                            images, targets = self.collate_mixup(
                                images, targets, mrng)
                        t3 = time.monotonic()
                    stats.load_s += t1 - t0
                    stats.collate_s += t2 - t1
                    stats.mixup_s += t3 - t2
                    stats.batches += 1
                    if vms is not None:
                        item: Any = (images, targets, vms[bi])
                    else:
                        item = (images, targets)
                    if not put(item, bi):
                        return
                put(None, len(batches))

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is None:
                    return
                yield item
        finally:
            stop.set()


def _identity_prologue(images, key):
    return images


class DeviceLoader:
    """Device-side prologue with async double buffering.

    The jitted prologue does uint8→``dtype`` cast, mean/std normalize (×255,
    tiled per frame), and train-time RandomErasing — the body of the
    reference's ``PrefetchLoader_v3.__iter__`` (loader.py:242-266) as one
    compiled function.  Because JAX dispatch is asynchronous, iterating one
    batch ahead gives the same copy/compute overlap the reference builds from
    CUDA streams.
    """

    def __init__(self, loader: HostLoader,
                 mean=IMAGENET_DEFAULT_MEAN, std=IMAGENET_DEFAULT_STD,
                 dtype: Any = jnp.bfloat16, re_prob: float = 0.0,
                 re_mode: str = "const", re_count: int = 1,
                 re_num_splits: int = 0, re_max: float = 0.1,
                 img_num: int = 4, seed: int = 0,
                 sharding: Optional[Any] = None,
                 color_jitter=None, flicker: float = 0.0,
                 stem_s2d: bool = False, device_augment: Optional[Any] = None,
                 identity_prologue: bool = False):
        self.loader = loader
        self.img_num = img_num
        self.stem_s2d = stem_s2d
        self.dtype = dtype
        self.sharding = sharding
        self.seed = seed
        self.stats = LoaderStats()
        # --augment-device on: a DeviceAugmentSpec (device_augment.py); the
        # host transform is then the raw-source passthrough and warp/blur/
        # mixup render here, keyed by the absolute (seed, epoch, index) /
        # (seed, epoch, batch) numpy streams the host chain would draw from
        self._augment = device_augment
        self.augment_device = device_augment is not None
        mean = np.tile(np.asarray(mean, np.float32) * 255.0, img_num)
        std = np.tile(np.asarray(std, np.float32) * 255.0, img_num)
        self._mean = mean.reshape(1, 1, 1, -1)
        self._std = std.reshape(1, 1, 1, -1)
        self.random_erasing = RandomErasing(
            probability=re_prob, max_area=re_max, mode=re_mode,
            max_count=re_count, num_splits=re_num_splits,
            img_num=img_num) if re_prob > 0.0 else None
        self._step = 0
        if identity_prologue:
            # token ids (create_token_loader) have nothing to cast, normalize
            # or erase: the staged array is the step's input
            self._prologue = jax.jit(_identity_prologue)
            return

        mean_j = jnp.asarray(self._mean)
        std_j = jnp.asarray(self._std)
        erasing = self.random_erasing
        from .device_augment import make_device_color_jitter
        jitter = make_device_color_jitter(color_jitter, flicker, img_num)
        if stem_s2d:
            # lazy: pulls flax via ops; only the consumer process (which
            # already built the model) constructs a DeviceLoader
            from ..ops.conv import space_to_depth
        else:
            space_to_depth = None
        if device_augment is not None:
            from .device_augment import (device_mixup_blend, make_device_blur,
                                         make_device_geometric)
            warp = make_device_geometric(device_augment)
            blur = make_device_blur(device_augment) \
                if device_augment.blur_prob > 0.0 else None
            mix_blocks = device_augment.mixup_blocks
            mix_on = device_augment.mixup
        else:
            warp = blur = None
            device_mixup_blend = None
            mix_blocks, mix_on = 1, False

        # ONE jitted prologue — single dispatch per batch.  Documented op
        # order (augment → normalize → s2d): warp → blur → jitter/flicker →
        # mixup blend → cast → normalize → RandomErasing → s2d pixel
        # shuffle.  That is the host chain's order (geometric → blur →
        # jitter → flicker → collate mixup → prologue), with the s2d stem
        # shuffle folded in last exactly as the two-stage path applied it
        # after normalize.
        def prologue(images, key, geom=None, blur_mask=None,
                     lam=None, one_minus_lam=None):
            # jitter operates in 0..255 float space BEFORE normalize, like
            # the host PIL chain it replaces (device_augment.py)
            jkey, ekey = jax.random.split(key)
            if warp is not None:
                x = warp(images, geom)             # f32, integer-valued
                if blur is not None:
                    x = blur(x, blur_mask)
                if jitter is not None:
                    x = jitter(x, jkey)
                if mix_on:
                    x = device_mixup_blend(x, lam, one_minus_lam,
                                           mix_blocks)
                x = x.astype(dtype)
            else:
                x = images.astype(jnp.float32 if jitter is not None
                                  else dtype)
                if jitter is not None:
                    x = jitter(x, jkey).astype(dtype)
            x = (x.astype(dtype) - mean_j.astype(dtype)) / std_j.astype(dtype)
            if erasing is not None:
                x = erasing(ekey, x).astype(dtype)
            if space_to_depth is not None:
                # s2d stem (PERF.md post-fusion roofline): ship the pixel
                # shuffle with the prologue so the (B, H/2, W/2, 4C) layout
                # lands on device once — the model consumes it directly
                # instead of re-shuffling every step
                x = space_to_depth(x)
            return x

        # NOTE: donating the uint8 wire buffer here would be a no-op — XLA
        # input->output aliasing needs matching byte sizes and the output is
        # 2-4x wider (bf16/f32); refcounting already frees the temporary
        self._prologue = jax.jit(prologue)

    # pass-throughs (reference :274-289)
    @property
    def sampler(self):
        return self.loader.sampler

    @property
    def dataset(self):
        return self.loader.dataset

    @property
    def mixup_enabled(self) -> bool:
        cm = self.loader.collate_mixup
        return bool(cm and cm.mixup_enabled)

    @mixup_enabled.setter
    def mixup_enabled(self, x: bool) -> None:
        if self.loader.collate_mixup is not None:
            self.loader.collate_mixup.mixup_enabled = x

    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)
        # pin the prologue key stream to the ABSOLUTE position: every epoch
        # stages exactly len(loader) batches, so _step == epoch * len at an
        # epoch start in ANY run — a no-op for an uninterrupted run, and
        # the thing that makes a freshly-constructed loader's RandomErasing/
        # jitter keys bit-identical to the original run's after auto-resume
        self._step = epoch * len(self.loader)

    def fast_forward(self, start_batch: int) -> None:
        """Resume mid-epoch: the next iteration yields batches from
        ``start_batch`` on, bit-identical to the tail of a full epoch
        (host loaders keep absolute batch indices for their per-batch RNG;
        the prologue key stream advances to match).  Call AFTER
        :meth:`set_epoch`; cleared by the next ``set_epoch``."""
        if start_batch <= 0:
            return
        if not hasattr(self.loader, "start_batch"):
            raise NotImplementedError(
                f"{type(self.loader).__name__} cannot fast-forward")
        self.loader.start_batch = int(start_batch)
        self._step += int(start_batch)

    def close(self) -> None:
        """Tear down the host loader's workers/shm (no-op for threads)."""
        close = getattr(self.loader, "close", None)
        if close is not None:
            close()

    def __len__(self) -> int:
        return len(self.loader)

    def _put(self, arr: np.ndarray):
        if self.sharding is not None:
            from ..parallel.sharding import put_process_local
            return put_process_local(arr, self.sharding)
        return jax.device_put(arr)

    def _stage(self, item, base_key, batch_index: int = 0,
               indices: Optional[Sequence[int]] = None):
        """device_put + dispatch the prologue for one host batch.  Returns
        the staged batch and the images' device copy (what the prologue
        reads: alive until it has run in any case, nothing is donated)."""
        images, targets = item[0], item[1]
        key = jax.random.fold_in(base_key, self._step)
        self._step += 1
        if self._augment is not None:
            from .device_augment import (derive_geometric_batch,
                                         derive_mixup_lam)
            if indices is None or len(indices) != images.shape[0]:
                raise RuntimeError(
                    "--augment-device: per-sample indices out of step with "
                    f"the host batch ({None if indices is None else len(indices)} "
                    f"vs {images.shape[0]} rows)")
            geom, blur_mask = derive_geometric_batch(
                self._augment, indices, self.loader.seed, self.loader.epoch,
                images.shape[1:3])
            if self._augment.mixup:
                cm = self.loader.collate_mixup
                lam, om = derive_mixup_lam(
                    self.loader.seed, self.loader.epoch, batch_index,
                    self._augment.mixup_alpha,
                    bool(cm is not None and cm.mixup_enabled))
            else:
                lam, om = np.float32(1.0), np.float32(0.0)
            put = self._put(images)
            x = self._prologue(put, key, self._put(geom),
                               self._put(blur_mask), lam, om)
            self.stats.augment_elided += \
                images.shape[0] * self._augment.host_stages_elided
        else:
            put = self._put(images)
            x = self._prologue(put, key)
        # targets/valid views may be ring-slab backed: small, copy before
        # the put so slot recycling can never touch them
        y = self._put(np.array(targets))
        if len(item) == 3:
            return (x, y, self._put(np.array(item[2]))), put
        return (x, y), put

    def __iter__(self):
        base_key = jax.random.PRNGKey(self.seed)
        batches = None
        if self._augment is not None:
            # the device side re-derives each sample's augment parameters
            # from (seed, epoch, index): recompute the host loaders' exact
            # (epoch, batch) → indices mapping (epoch_batches is a pure
            # function of the shared sampler state, and both backends
            # front-end through it)
            batches, _ = epoch_batches(self.loader.sampler,
                                       self.loader.batch_size, False)
        bi = getattr(self.loader, "start_batch", 0)
        it = iter(self.loader)
        # double buffering: stage batch k+1 (host→device transfer +
        # prologue dispatch) BEFORE yielding batch k, so the transfer
        # overlaps the consumer's compiled step on batch k — the async-
        # dispatch equivalent of the reference's CUDA-stream prefetcher.
        pending = None
        prev_x = prev_put = None
        stats = self.stats
        while True:
            if prev_x is not None:
                # the shm ring recycles batch k's slab once batch k+2 is
                # requested; jax CPU device_put zero-copies aligned host
                # buffers, so batch k's prologue (the only reader of the
                # slab) must have RUN before we pull the next host batch.
                # One chain, awaited in two places: the copy the prologue
                # reads, then the prologue itself (which depends on it).  A
                # long first wait on an idle device is the copy starving
                # the chip; on a busy device the two waits trade places
                # and their sum is the chip's backlog
                t0 = time.monotonic()
                with TraceAnnotation("dfd.input.stage_block", batch=bi - 1):
                    with TraceAnnotation("dfd.input.h2d_block",
                                         batch=bi - 1):
                        jax.block_until_ready(prev_put)
                    t1 = time.monotonic()
                    with TraceAnnotation("dfd.input.prologue_block",
                                         batch=bi - 1):
                        jax.block_until_ready(prev_x)   # batch bi-1's
                t2 = time.monotonic()
                stats.h2d_block_s += t1 - t0
                stats.prologue_block_s += t2 - t1
                stats.stage_block_s += t2 - t0
                prev_x = prev_put = None
            try:
                t0 = time.monotonic()
                with TraceAnnotation("dfd.input.host_wait", batch=bi):
                    item = next(it)
                stats.host_wait_s += time.monotonic() - t0
            except StopIteration:
                break
            t0 = time.monotonic()
            with TraceAnnotation("dfd.input.stage", batch=bi):
                staged, put = self._stage(item, base_key, batch_index=bi,
                                          indices=None if batches is None
                                          else batches[bi])
            stats.stage_s += time.monotonic() - t0
            bi += 1
            stats.batches += 1
            if pending is not None:
                prev_x, prev_put = staged[0], put
                yield pending
            pending = staged
        if pending is not None:
            yield pending


def _build_loader(dataset, transform, batch_size: int, is_training: bool,
                  num_aug_splits: int, collate_mixup, distributed: bool,
                  num_shards: int, shard_index: int, seed: int,
                  num_workers: int, prefetch_depth: int,
                  valid_mask: Optional[bool],
                  device_kwargs: dict, loader_backend: str = "thread",
                  ring_depth: int = 4,
                  worker_heartbeat: float = 120.0) -> DeviceLoader:
    """Shared factory tail: AugMix wrap, transform attach, sharded sampler
    selection, host loader backend, device prologue.  Both
    :func:`create_loader` and :func:`create_deepfake_loader_v3` end here."""
    hw = getattr(dataset, "packed_hw", None)
    if hw is not None:
        # packed pre-decoded cache: the pack replaces the decode STAGE
        # only — transform, sampler, collate and transport below are the
        # shared code paths.  A pack smaller than the crop would make
        # pad_if_needed silently diverge from the decode path: warn loud.
        crop = getattr(transform.transforms[0], "size", None) \
            if getattr(transform, "transforms", None) else None
        if crop is not None and isinstance(crop, tuple) and \
                (crop[0] > hw[0] or crop[1] > hw[1]):
            _logger.warning(
                "packed cache resolution %s is below the crop %s: crops "
                "will pad, diverging from the decode path — re-pack with "
                "a larger --pack-image-size", hw, crop)
    if is_training and num_aug_splits > 1:
        # clean + (num_aug_splits-1) AugMix views per sample, feeding the
        # JSD consistency loss (reference dataset.py:633-670)
        assert collate_mixup is None, \
            "aug_splits and the mixup collate are mutually exclusive " \
            "(reference train.py:446)"
        from .dataset import AugMixDataset
        dataset = AugMixDataset(dataset, num_splits=num_aug_splits)
    dataset.set_transform(transform)

    if not distributed:
        num_shards, shard_index = 1, 0
    if is_training:
        sampler: Any = ShardedTrainSampler(
            len(dataset), num_shards=num_shards, shard_index=shard_index,
            batch_size=batch_size, seed=seed, drop_last=True)
    else:
        sampler = OrderedShardedSampler(
            len(dataset), num_shards=num_shards, shard_index=shard_index,
            batch_size=batch_size)
    if valid_mask is None:
        valid_mask = not is_training
    if loader_backend == "shm":
        from .shm_ring import ShmRingLoader
        host: Any = ShmRingLoader(
            dataset, sampler, batch_size, seed=seed,
            num_workers=num_workers, ring_depth=ring_depth,
            collate_mixup=collate_mixup if is_training else None,
            valid_mask=valid_mask, heartbeat_timeout=worker_heartbeat)
    elif loader_backend == "thread":
        host = HostLoader(dataset, sampler, batch_size, seed=seed,
                          num_workers=num_workers,
                          prefetch_depth=prefetch_depth,
                          collate_mixup=collate_mixup if is_training else None,
                          valid_mask=valid_mask)
    else:
        raise ValueError(f"loader_backend must be one of {LOADER_BACKENDS}, "
                         f"got {loader_backend!r}")
    return DeviceLoader(host, seed=seed, **device_kwargs)


def create_token_loader(
        dataset, batch_size: int, is_training: bool = False,
        num_workers: int = 1, distributed: bool = False,
        num_shards: int = 1, shard_index: int = 0, seed: int = 42,
        prefetch_depth: int = 2, sharding: Optional[Any] = None,
        valid_mask: Optional[bool] = None) -> DeviceLoader:
    """Loader of a token dataset (data/tokens.py): the sharded samplers, the
    thread-backed :class:`HostLoader` and the :class:`DeviceLoader` staging
    as every other loader has them (the same spans and counters), int32 on
    the wire and the identity for a prologue."""
    return _build_loader(
        dataset, None, batch_size, is_training, 0, None, distributed,
        num_shards, shard_index, seed, num_workers, prefetch_depth,
        valid_mask, dict(sharding=sharding, identity_prologue=True))


def create_loader(
        dataset, input_size, batch_size: int, is_training: bool = False,
        re_prob: float = 0.0, re_mode: str = "const", re_count: int = 1,
        re_split: bool = False, re_max: float = 0.02,
        color_jitter: Any = 0.4,
        auto_augment: Optional[str] = None, num_aug_splits: int = 0,
        interpolation: str = "bilinear",
        mean=IMAGENET_DEFAULT_MEAN, std=IMAGENET_DEFAULT_STD,
        num_workers: int = 1, distributed: bool = False,
        num_shards: int = 1, shard_index: int = 0,
        crop_pct: Optional[float] = None,
        collate_mixup: Optional[FastCollateMixup] = None,
        dtype: Any = jnp.bfloat16, tf_preprocessing: bool = False,
        seed: int = 42, prefetch_depth: int = 2,
        sharding: Optional[Any] = None, valid_mask: Optional[bool] = None,
        loader_backend: str = "thread", ring_depth: int = 4,
        worker_heartbeat: float = 120.0, stem_s2d: bool = False,
        ) -> DeviceLoader:
    """Generic single-image loader factory (reference loader.py:372-456).

    The timm-style path for training the backbone families on folder /
    tar / synthetic datasets — the deepfake clip path is
    :func:`create_deepfake_loader_v3`.  Reference knobs map as: torch
    ``DistributedSampler``/``OrderedDistributedSampler`` → the sharded
    samplers (``distributed`` + ``num_shards``/``shard_index``);
    ``use_prefetcher``/``fp16``/``pin_memory``/CUDA streams → the always-on
    uint8-wire :class:`DeviceLoader` with ``dtype``; ``collate_fn`` →
    ``collate_mixup`` (the only non-default collate the reference ever
    passes, train.py:444).
    """
    from .transforms_factory import create_transform

    re_num_splits = 0
    if re_split:
        # RE on the second half of the batch, or aligned with aug splits
        # (reference :397-399)
        re_num_splits = num_aug_splits or 2
    # the host transform uses mean only (auto-augment fill color);
    # normalization with mean AND std happens in the device prologue, so
    # std is deliberately not forwarded here
    transform = create_transform(
        input_size, is_training=is_training, color_jitter=color_jitter,
        auto_augment=auto_augment, interpolation=interpolation, mean=mean,
        crop_pct=crop_pct, tf_preprocessing=tf_preprocessing)
    return _build_loader(
        dataset, transform, batch_size, is_training, num_aug_splits,
        collate_mixup, distributed, num_shards, shard_index, seed,
        num_workers, prefetch_depth, valid_mask,
        dict(mean=mean, std=std, dtype=dtype,
             re_prob=re_prob if is_training else 0.0, re_mode=re_mode,
             re_count=re_count, re_num_splits=re_num_splits, re_max=re_max,
             img_num=1, sharding=sharding, stem_s2d=stem_s2d),
        loader_backend=loader_backend, ring_depth=ring_depth,
        worker_heartbeat=worker_heartbeat)


def create_deepfake_loader_v3(
        dataset, input_size, batch_size: int, is_training: bool = False,
        re_prob: float = 0.0, re_mode: str = "const", re_count: int = 1,
        re_split: bool = False, re_max: float = 0.02,
        color_jitter: Any = 0.4, num_aug_splits: int = 0,
        mean=IMAGENET_DEFAULT_MEAN, std=IMAGENET_DEFAULT_STD,
        num_workers: int = 1, distributed: bool = False,
        num_shards: int = 1, shard_index: int = 0,
        collate_mixup: Optional[FastCollateMixup] = None,
        dtype: Any = jnp.bfloat16, flicker: float = 0.0,
        rotate_range: float = 0, blur_radius: Optional[float] = None,
        blur_prob: float = 0.0, seed: int = 42, prefetch_depth: int = 2,
        sharding: Optional[Any] = None, valid_mask: Optional[bool] = None,
        eval_crop: str = "random", device_color_jitter: bool = True,
        fused_geom: bool = True, loader_backend: str = "thread",
        ring_depth: int = 4, worker_heartbeat: float = 120.0,
        stem_s2d: bool = False, augment_device: bool = False,
        blur_radiu: Optional[float] = None,
        ) -> DeviceLoader:
    """Loader factory (reference loader.py:724-830): builds the v3 transform,
    picks the train/eval sharded sampler, wires collate mixup and the device
    prologue.

    ``device_color_jitter`` (default) moves ColorJitter/Flicker off the host
    into the jitted device prologue (device_augment.py); ``fused_geom``
    (default) renders the geometric chain as one native warp — together they
    cut host cost per clip ~3× at the flagship shape.  Disabling both
    restores the reference-exact host PIL pipeline.

    ``augment_device`` (``--augment-device on``) moves the REMAINING host
    augment — the geometric warp, per-frame blur, and the mixup blend —
    into the same jitted prologue, keyed by the identical absolute numpy
    RNG streams (device_augment.py); the host transform collapses to a
    raw-source passthrough and host input cost becomes the collate/slab
    memcpy.  Falls back to the host chain (with a log line) for the
    host-only stages: AugMix aug-splits and hue jitter.  ``blur_radiu``
    is the deprecated alias of ``blur_radius``."""
    from .transforms_factory import _blur_radius_compat
    blur_radius = _blur_radius_compat(blur_radius, blur_radiu)
    re_num_splits = 0
    if re_split:
        re_num_splits = num_aug_splits or 2
    img_size = input_size[-2:] if isinstance(input_size, (tuple, list)) \
        else input_size
    if isinstance(img_size, (tuple, list)) and len(img_size) == 2:
        img_size = img_size[0] if img_size[0] == img_size[1] else tuple(img_size)

    aug_device = bool(augment_device and is_training)
    if aug_device and num_aug_splits > 1:
        # the AugMix view augmentation is a host PIL op chain applied to
        # the POST-geometric clip; warping on device would reorder it —
        # keep the host chain rather than silently change what the JSD
        # loss measures
        _logger.info("aug-splits active: device augmentation falls back "
                     "to the host chain")
        aug_device = False
    if aug_device and not fused_geom:
        raise ValueError("augment_device renders the fused geometric warp "
                         "on device; it conflicts with the host_geom / "
                         "fused_geom=False parity escape hatch — pick one")

    device_cj = None
    device_flicker = 0.0
    if is_training and device_color_jitter:
        cj = None
        if color_jitter is not None:
            cj = (color_jitter if isinstance(color_jitter, (list, tuple))
                  else (float(color_jitter),) * 3)
            assert len(cj) in (3, 4)
        if cj is not None and len(cj) == 4 and float(cj[3]) > 0:
            # hue jitter is host-only (HSV round-trip not implemented on
            # device): keep the full PIL chain rather than silently
            # dropping the hue component
            _logger.info("hue jitter requested: color jitter stays on host")
            if aug_device:
                _logger.info("hue jitter requested: device augmentation "
                             "falls back to the host chain")
                aug_device = False
        elif aug_device:
            # the device prologue preserves the host order (jitter BEFORE
            # the mixup blend, device_augment.py op order), so jitter/
            # flicker ride the device even under mixup here
            device_cj = tuple(float(v) for v in cj[:3]) if cj else None
            device_flicker, flicker = flicker, 0.0
            color_jitter = None
        elif collate_mixup is not None and is_training:
            # the host chain jitters each source clip BEFORE mixup blends
            # them; a post-blend device jitter would correlate the two
            # sources' photometrics — keep host order under mixup
            _logger.info("mixup active: color jitter stays on host")
        elif num_aug_splits > 1:
            # AugMix views of one sample share the base transform's single
            # jitter draw (host chain); as separate batch rows they would
            # get INDEPENDENT device draws, changing what the JSD
            # consistency loss measures — keep host jitter under aug-splits
            _logger.info("aug-splits active: color jitter stays on host")
        else:
            device_cj = tuple(float(v) for v in cj[:3]) if cj else None
            device_flicker, flicker = flicker, 0.0
            color_jitter = None
    if aug_device and (color_jitter is not None or flicker > 0.0):
        # --host-color-jitter with --augment-device: the passthrough chain
        # has no host jitter/flicker stage to run them in
        raise ValueError(
            "augment_device leaves no host transform stage for host-side "
            "color jitter/flicker — drop host_color_jitter (hue jitter "
            "already falls back to the host chain automatically)")

    device_augment = None
    if is_training:
        if aug_device:
            from .device_augment import DeviceAugmentSpec
            from .transforms_factory import \
                transforms_deepfake_train_passthrough
            size2 = (img_size, img_size) if isinstance(img_size, int) \
                else tuple(img_size)
            img_num_ = int(input_size[0] / 3) \
                if isinstance(input_size, (tuple, list)) else 1
            device_augment = DeviceAugmentSpec(
                size=size2, rotate_range=int(rotate_range),
                blur_prob=float(blur_prob),
                blur_radius=float(blur_radius or 0.0),
                img_num=max(1, img_num_),
                mixup=collate_mixup is not None,
                mixup_alpha=getattr(collate_mixup, "mixup_alpha", 0.0),
                # the host collate mixes within each PROCESS's local
                # batch; the device blend flips within matching blocks
                mixup_blocks=num_shards if distributed else 1)
            if collate_mixup is not None:
                collate_mixup.blend = False     # lam + soft targets only
            if getattr(dataset, "packed_hw", None) is None:
                _logger.info(
                    "augment_device without a packed cache: the decode "
                    "path must yield one uniform source geometry (the "
                    "warp compiles per source shape)")
            transform = transforms_deepfake_train_passthrough(
                img_size, rotate_range=rotate_range, blur_prob=blur_prob)
        else:
            transform = transforms_deepfake_train_v3(
                img_size, color_jitter=color_jitter, flicker=flicker,
                rotate_range=rotate_range, blur_radius=blur_radius,
                blur_prob=blur_prob, fused_geom=fused_geom)
    else:
        transform = transforms_deepfake_eval_v3(img_size, crop=eval_crop)
    img_num = int(input_size[0] / 3) if isinstance(input_size, (tuple, list)) \
        else 1
    return _build_loader(
        dataset, transform, batch_size, is_training, num_aug_splits,
        collate_mixup, distributed, num_shards, shard_index, seed,
        num_workers, prefetch_depth, valid_mask,
        dict(mean=mean, std=std, dtype=dtype,
             re_prob=re_prob if is_training else 0.0, re_mode=re_mode,
             re_count=re_count, re_num_splits=re_num_splits, re_max=re_max,
             img_num=max(1, img_num), sharding=sharding,
             color_jitter=device_cj, flicker=device_flicker,
             stem_s2d=stem_s2d, device_augment=device_augment),
        loader_backend=loader_backend, ring_depth=ring_depth,
        worker_heartbeat=worker_heartbeat)
