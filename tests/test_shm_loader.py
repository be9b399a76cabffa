"""Shared-memory ring loader (data/shm_ring.py).

The contract under test: the ``shm`` backend is a drop-in for the
``thread`` backend — bit-identical batches for any worker count, across
epochs, through every collate variant (plain, valid-mask eval, mixup,
AugMix split-major) — plus the robustness properties the thread pool never
needed: worker-crash respawn, abandoned-iterator quiesce, and shm-segment
cleanup on close.
"""

import os
import time

import numpy as np
import pytest
from PIL import Image

from deepfake_detection_tpu.data import (DeepFakeClipDataset,
                                         FastCollateMixup, SyntheticDataset,
                                         create_deepfake_loader_v3)
from deepfake_detection_tpu.data.loader import HostLoader
from deepfake_detection_tpu.data.samplers import (OrderedShardedSampler,
                                                  ShardedTrainSampler,
                                                  epoch_batches)
from deepfake_detection_tpu.data.shm_ring import ShmRingLoader
from deepfake_detection_tpu.data.transforms_factory import \
    transforms_deepfake_train_v3

pytestmark = pytest.mark.smoke


def _make_clip_tree(root, n_real=3, n_fake=3, size=48, frames=4):
    os.makedirs(root, exist_ok=True)
    g = np.random.default_rng(0)
    for kind, n in (("real", n_real), ("fake", n_fake)):
        lines = []
        for i in range(n):
            d = os.path.join(root, kind, f"{kind}clip{i}")
            os.makedirs(d, exist_ok=True)
            for j in range(frames):
                Image.fromarray(g.integers(0, 255, (size, size, 3),
                                           dtype=np.uint8)).save(
                    os.path.join(d, f"{j}.jpg"))
            lines.append(f"{kind}clip{i}:{frames}")
        with open(os.path.join(root, f"{kind}_list.txt"), "w") as f:
            f.write("\n".join(lines) + "\n")


def _drain(loader, epochs=1):
    out = []
    for e in range(epochs):
        loader.set_epoch(e)
        # yielded images are ring-slab views valid for 2 more pulls —
        # copy at collection time, exactly what the contract requires
        out.append([tuple(np.array(part) for part in item)
                    for item in loader])
    return out


def _assert_epochs_equal(a, b):
    assert len(a) == len(b)
    for ea, eb in zip(a, b):
        assert len(ea) == len(eb) and len(ea) > 0
        for ia, ib in zip(ea, eb):
            assert len(ia) == len(ib)
            for xa, xb in zip(ia, ib):
                np.testing.assert_array_equal(xa, xb)


class CrashOnceDataset:
    """Picklable wrapper that hard-kills the FIRST worker process to load
    ``crash_index`` (a sentinel file makes the respawned worker succeed).
    The parent probe is protected by the pid guard."""

    def __init__(self, base, sentinel, crash_index, parent_pid):
        self.base = base
        self.sentinel = sentinel
        self.crash_index = crash_index
        self.parent_pid = parent_pid

    def set_epoch(self, epoch):
        self.base.set_epoch(epoch)

    def set_transform(self, transform):
        self.base.set_transform(transform)

    def __len__(self):
        return len(self.base)

    def __getitem__(self, index, rng=None):
        if (index == self.crash_index and os.getpid() != self.parent_pid
                and not os.path.exists(self.sentinel)):
            open(self.sentinel, "w").close()
            # die mid-sample, not mid-ack: a kill while this worker's queue
            # feeder thread is still writing an earlier sample's ack leaves
            # done_q's write lock held for good, and no ack of any worker
            # arrives again (seen under a loaded box; shm_ring.py's
            # lost-ack net re-dispatches but cannot free the lock)
            time.sleep(0.5)
            os._exit(3)
        return self.base.__getitem__(index, rng=rng)


# ---------------------------------------------------------------------------
# Bit-identity: thread ↔ shm
# ---------------------------------------------------------------------------

class TestShmThreadBitIdentity:
    @pytest.mark.parametrize("workers", [1, 3])
    def test_synthetic_across_epochs(self, workers):
        mk = lambda cls, ds, **kw: cls(
            ds, ShardedTrainSampler(16, batch_size=4, seed=7), 4, seed=7,
            num_workers=workers, **kw)
        h = mk(HostLoader, SyntheticDataset(16, (24, 24, 12)))
        s = mk(ShmRingLoader, SyntheticDataset(16, (24, 24, 12)))
        try:
            _assert_epochs_equal(_drain(h, epochs=2), _drain(s, epochs=2))
        finally:
            s.close()

    def test_jpeg_clips_full_transform(self, tmp_path):
        """Real decode + the production v3 transform chain through worker
        processes matches the thread pool bit-for-bit."""
        root = str(tmp_path / "clips")
        _make_clip_tree(root)

        def build():
            ds = DeepFakeClipDataset(root)
            ds.set_transform(transforms_deepfake_train_v3(
                32, color_jitter=None, rotate_range=5, blur_radius=1,
                blur_prob=0.2))
            return ds

        sam = lambda n: ShardedTrainSampler(n, batch_size=3, seed=0)
        h = HostLoader(build(), sam(6), 3, seed=0, num_workers=2)
        s = ShmRingLoader(build(), sam(6), 3, seed=0, num_workers=2)
        try:
            _assert_epochs_equal(_drain(h, epochs=2), _drain(s, epochs=2))
        finally:
            s.close()

    def test_eval_valid_mask(self):
        """Masked-eval path: identical images, targets AND padding masks."""
        mk = lambda cls: cls(
            SyntheticDataset(10, (16, 16, 12)),
            OrderedShardedSampler(10, batch_size=4), 4, seed=3,
            num_workers=2, valid_mask=True)
        h, s = mk(HostLoader), mk(ShmRingLoader)
        try:
            a, b = _drain(h), _drain(s)
            _assert_epochs_equal(a, b)
            assert all(len(item) == 3 for item in a[0])
            # padded to 3 batches of 4; exactly dataset_len rows are valid
            assert sum(int(item[2].sum()) for item in a[0]) == 10
        finally:
            s.close()

    def test_collate_mixup(self):
        """Mixup blends on the consumer side from the batch RNG stream —
        soft targets and blended uint8 images must match the thread path."""
        mk = lambda cls: cls(
            SyntheticDataset(12, (16, 16, 12)),
            ShardedTrainSampler(12, batch_size=4, seed=5), 4, seed=5,
            num_workers=2,
            collate_mixup=FastCollateMixup(1.0, 0.1, num_classes=2))
        h, s = mk(HostLoader), mk(ShmRingLoader)
        try:
            a, b = _drain(h), _drain(s)
            _assert_epochs_equal(a, b)
            assert a[0][0][1].dtype == np.float32         # soft targets
        finally:
            s.close()

    def test_factory_device_outputs_match(self):
        """--loader-backend thread vs shm end-to-end through the jitted
        device prologue: identical float batches."""
        import jax.numpy as jnp

        def batches(backend):
            loader = create_deepfake_loader_v3(
                SyntheticDataset(8, (24, 24, 12)), (12, 24, 24),
                batch_size=4, is_training=True, num_workers=2,
                dtype=jnp.float32, re_prob=0.2, re_max=0.1,
                loader_backend=backend)
            try:
                return [(np.asarray(x), np.asarray(y)) for x, y in loader]
            finally:
                loader.close()

        a, b = batches("thread"), batches("shm")
        assert len(a) == len(b) == 2
        for (xa, ya), (xb, yb) in zip(a, b):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)

    def test_aug_splits_split_major(self):
        """AugMix multi-view samples land split-major in the slab exactly
        as fast_collate lays them out, labels tiled."""
        import jax.numpy as jnp

        def batch(backend):
            loader = create_deepfake_loader_v3(
                SyntheticDataset(4, (16, 16, 3)), (3, 16, 16),
                batch_size=2, is_training=True, num_aug_splits=2,
                num_workers=2, dtype=jnp.float32, loader_backend=backend)
            try:
                x, y = next(iter(loader))
                return np.asarray(x), np.asarray(y)
            finally:
                loader.close()

        xa, ya = batch("thread")
        xb, yb = batch("shm")
        assert xa.shape == (4, 16, 16, 3)        # splits x batch rows
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)


# ---------------------------------------------------------------------------
# Robustness
# ---------------------------------------------------------------------------

class TestShmRobustness:
    def test_worker_crash_respawn(self, tmp_path):
        """A worker hard-killed mid-sample is respawned and its one lost
        task re-dispatched; the epoch completes bit-identical to the
        thread loader (deterministic samples make recovery idempotent)."""
        sampler = ShardedTrainSampler(12, batch_size=4, seed=2)
        crash_index = epoch_batches(sampler, 4)[0][1][0]  # batch 1, not probe
        ds = CrashOnceDataset(SyntheticDataset(12, (16, 16, 12)),
                              str(tmp_path / "crashed"), crash_index,
                              os.getpid())
        s = ShmRingLoader(ds, sampler, 4, seed=2, num_workers=2,
                          ring_depth=3)
        h = HostLoader(SyntheticDataset(12, (16, 16, 12)),
                       ShardedTrainSampler(12, batch_size=4, seed=2), 4,
                       seed=2, num_workers=1)
        try:
            _assert_epochs_equal(_drain(h), _drain(s))
            assert s.respawn_count >= 1
            assert os.path.exists(str(tmp_path / "crashed"))
        finally:
            s.close()

    def test_sample_error_raises_not_hangs(self, tmp_path):
        """A dataset exception inside a worker surfaces as a consumer-side
        RuntimeError naming the sample — not a dead worker, not a hang."""
        import shutil
        root = str(tmp_path / "clips")
        _make_clip_tree(root, size=24)
        ds = DeepFakeClipDataset(root)
        ds.set_transform(transforms_deepfake_train_v3(16, color_jitter=None))
        sampler = ShardedTrainSampler(len(ds), batch_size=3, seed=1)
        probe = next(iter(sampler))
        # break a clip that is NOT the parent-side probe sample, so the
        # failure happens inside a worker process
        broken = next(i for i in range(len(ds)) if i != probe)
        shutil.rmtree(os.path.dirname(ds.sample_paths(broken)[0][0]))
        s = ShmRingLoader(ds, sampler, 3, seed=1, num_workers=2)
        try:
            with pytest.raises(RuntimeError, match="shm worker failed"):
                _drain(s)
        finally:
            s.close()

    def test_shm_cleanup_on_close(self):
        from multiprocessing import shared_memory
        s = ShmRingLoader(SyntheticDataset(8, (16, 16, 12)),
                          ShardedTrainSampler(8, batch_size=4, seed=0), 4,
                          seed=0, num_workers=2)
        it = iter(s)
        next(it)
        name = s._ring.name
        workers = list(s._workers)
        it.close()
        s.close()
        with pytest.raises(FileNotFoundError):
            shared_memory.SharedMemory(name=name)
        for p in workers:
            assert p.exitcode is not None        # all workers exited
        s.close()                                # idempotent

    def test_abandoned_iterator_then_clean_reuse(self):
        """Breaking mid-epoch leaves in-flight tasks; the next iteration
        quiesces them (generation bump) and still produces exact batches."""
        ds1, ds2 = (SyntheticDataset(16, (16, 16, 12)) for _ in range(2))
        s = ShmRingLoader(ds1, ShardedTrainSampler(16, batch_size=4, seed=9),
                          4, seed=9, num_workers=2)
        h = HostLoader(ds2, ShardedTrainSampler(16, batch_size=4, seed=9),
                       4, seed=9, num_workers=1)
        try:
            for _ in s:          # abandon after the first batch
                break
            _assert_epochs_equal(_drain(h, epochs=2), _drain(s, epochs=2))
        finally:
            s.close()

    def test_ring_depth_floor_and_len(self):
        s = ShmRingLoader(SyntheticDataset(8, (8, 8, 3)),
                          ShardedTrainSampler(8, batch_size=4, seed=0), 4,
                          ring_depth=1)
        assert s.ring_depth == 3                 # double buffering minimum
        assert len(s) == 2
        s.close()                                # close before start: no-op
