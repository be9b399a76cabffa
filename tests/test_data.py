"""Data pipeline tests (SURVEY.md §4: deterministic-seed unit tests)."""

import os

import numpy as np
import pytest
from PIL import Image

from deepfake_detection_tpu.data import (DeepFakeClipDataset,
                                         FastCollateMixup, SyntheticDataset,
                                         create_deepfake_loader_v3,
                                         fast_collate, resolve_data_config)
from deepfake_detection_tpu.data import mixup as mixup_mod
from deepfake_detection_tpu.data.auto_augment import (
    augment_and_mix_transform, auto_augment_transform, rand_augment_transform)
from deepfake_detection_tpu.data.random_erasing import random_erasing
from deepfake_detection_tpu.data.samplers import (OrderedShardedSampler,
                                                  ShardedTrainSampler)
from deepfake_detection_tpu.data.transforms import (Compose, MultiConcate,
                                                    MultiRandomCrop,
                                                    MultiRandomHorizontalFlip,
                                                    MultiRandomResize,
                                                    MultiRotate, MultiToNumpy)
from deepfake_detection_tpu.data.transforms_factory import (
    transforms_deepfake_eval_v3, transforms_deepfake_train_v3)

pytestmark = pytest.mark.smoke  # fast tier: see pyproject [tool.pytest]


def _rng(seed=0):
    return np.random.default_rng(seed)


def _frames(n=4, size=(64, 48), seed=0):
    g = _rng(seed)
    return [Image.fromarray(
        g.integers(0, 255, (size[1], size[0], 3), dtype=np.uint8))
        for _ in range(n)]


# ---------------------------------------------------------------------------
# Multi* transforms
# ---------------------------------------------------------------------------

class TestMultiTransforms:
    def test_shared_flip(self):
        imgs = _frames()
        flipped = MultiRandomHorizontalFlip(p=1.0)(imgs, _rng())
        for orig, fl in zip(imgs, flipped):
            assert np.array_equal(np.asarray(fl),
                                  np.asarray(orig)[:, ::-1])

    def test_shared_resize_and_crop(self):
        imgs = _frames()
        out = MultiRandomResize(scale=(2. / 3, 3. / 2))(imgs, _rng(1))
        sizes = {im.size for im in out}
        assert len(sizes) == 1  # all frames share the same target size
        out = MultiRandomCrop(32, pad_if_needed=True)(out, _rng(2))
        assert all(im.size == (32, 32) for im in out)

    def test_rotate_shared_angle(self):
        imgs = _frames()
        out = MultiRotate(30)(imgs, _rng(3))
        assert len({im.size for im in out}) == 1  # expand=True, same canvas

    def test_concat_nhwc(self):
        imgs = _frames()
        arrs = MultiToNumpy()(imgs)
        cat = MultiConcate()(arrs)
        assert cat.shape == (48, 64, 12)
        assert cat.dtype == np.uint8

    def test_train_pipeline_shape_and_determinism(self):
        tf = transforms_deepfake_train_v3(
            600, color_jitter=0.4, flicker=0.05, rotate_range=5,
            blur_radius=1, blur_prob=0.05)
        imgs = _frames(4, size=(700, 500))
        a = tf(imgs, _rng(7))
        b = tf(imgs, _rng(7))
        c = tf(imgs, _rng(8))
        assert a.shape == (600, 600, 12) and a.dtype == np.uint8
        np.testing.assert_array_equal(a, b)  # same rng → same output
        assert not np.array_equal(a, c)

    def test_eval_pipeline(self):
        tf = transforms_deepfake_eval_v3(600)
        out = tf(_frames(4, size=(650, 620)), _rng())
        assert out.shape == (600, 600, 12)


# ---------------------------------------------------------------------------
# Dataset
# ---------------------------------------------------------------------------

def _make_v3_tree(root, n_real=3, n_fake=6, frames=(4, 2, 4, 4, 1, 3)):
    os.makedirs(root, exist_ok=True)
    real_lines, fake_lines = [], []
    for i in range(n_real):
        name = f"realclip{i}"
        d = os.path.join(root, "real", name)
        os.makedirs(d, exist_ok=True)
        nf = 4
        for j in range(nf):
            Image.new("RGB", (32, 32), (i, j, 0)).save(
                os.path.join(d, f"{j}.jpg"))
        real_lines.append(f"{name}:{nf}")
    for i in range(n_fake):
        name = f"fakeclip{i}"
        d = os.path.join(root, "fake", name)
        os.makedirs(d, exist_ok=True)
        nf = frames[i % len(frames)]
        for j in range(nf):
            Image.new("RGB", (32, 32), (i, j, 100)).save(
                os.path.join(d, f"{j}.jpg"))
        fake_lines.append(f"{name}:{nf}")
    with open(os.path.join(root, "real_list.txt"), "w") as f:
        f.write("\n".join(real_lines) + "\n")
    with open(os.path.join(root, "fake_list.txt"), "w") as f:
        f.write("\n".join(fake_lines) + "\n")


class TestDeepFakeClipDataset:
    def test_lengths_and_labels(self, tmp_path):
        root = str(tmp_path / "d")
        _make_v3_tree(root)
        ds = DeepFakeClipDataset(root)
        # no label_balance: every fake is its own bucket → 6 + 3
        assert len(ds) == 9
        paths, y = ds.sample_paths(0)
        assert y == 0 and len(paths) == 4
        paths, y = ds.sample_paths(len(ds) - 1)
        assert y == 1

    def test_short_clip_padding(self, tmp_path):
        root = str(tmp_path / "d")
        _make_v3_tree(root)
        ds = DeepFakeClipDataset(root)
        # fakeclip1 has 2 frames → padded with 0.jpg twice then frames 0,1
        idx = [i for i in range(len(ds))
               if "fakeclip1/" in ds.sample_paths(i)[0][0].replace(os.sep, "/")]
        paths, _ = ds.sample_paths(idx[0])
        names = [os.path.basename(p) for p in paths]
        assert names == ["0.jpg", "0.jpg", "0.jpg", "1.jpg"]

    def test_label_balance_rotation(self, tmp_path):
        root = str(tmp_path / "d")
        _make_v3_tree(root)
        ds = DeepFakeClipDataset(root, label_balance=True)
        # 6 fakes into 3 buckets of 2 → index space 3 fake + 3 real
        assert len(ds) == 6
        p0, _ = ds.sample_paths(0, epoch=0)
        p1, _ = ds.sample_paths(0, epoch=1)
        p2, _ = ds.sample_paths(0, epoch=2)
        assert p0 != p1          # rotation advances with epoch
        assert p0 == p2          # bucket size 2 → period 2

    def test_split_determinism(self, tmp_path):
        root = str(tmp_path / "d")
        _make_v3_tree(root, n_real=10, n_fake=10)
        tr1 = DeepFakeClipDataset(root, train_split=True, train_ratio=0.7,
                                  is_training=True, split_seed=5)
        tr2 = DeepFakeClipDataset(root, train_split=True, train_ratio=0.7,
                                  is_training=True, split_seed=5)
        va = DeepFakeClipDataset(root, train_split=True, train_ratio=0.7,
                                 is_training=False, split_seed=5)
        assert tr1.real_clips == tr2.real_clips
        names_tr = {c[0] for c in tr1.real_clips}
        names_va = {c[0] for c in va.real_clips}
        assert not names_tr & names_va
        assert len(names_tr) + len(names_va) == 10

    def test_getitem_with_transform(self, tmp_path):
        root = str(tmp_path / "d")
        _make_v3_tree(root)
        ds = DeepFakeClipDataset(root,
                                 transform=transforms_deepfake_eval_v3(32))
        img, y = ds[0]
        assert img.shape == (32, 32, 12)

    def test_tf_preprocessing_bridge(self):
        """TF-semantics bridge without TF (reference tf_preprocessing.py):
        eval crop-padding formula, train distorted-box sampling, uint8 HWC."""
        from deepfake_detection_tpu.data.tf_preprocessing import (
            CROP_PADDING, TfPreprocessTransform)
        from deepfake_detection_tpu.data.transforms_factory import \
            create_transform
        rng = np.random.default_rng(0)
        arr = rng.integers(0, 256, (300, 260, 3)).astype(np.uint8)

        ev = TfPreprocessTransform(is_training=False, size=224)
        out = ev(Image.fromarray(arr), rng)
        assert out.shape == (224, 224, 3) and out.dtype == np.uint8
        # deterministic and equal to the hand-computed crop window
        crop = int((224 / (224 + CROP_PADDING)) * 260)
        top, left = ((300 - crop) + 1) // 2, ((260 - crop) + 1) // 2
        np.testing.assert_array_equal(out, ev(arr, rng))
        assert crop == 227 and top == 37 and left == 17

        tr = TfPreprocessTransform(is_training=True, size=96)
        a = tr(arr, np.random.default_rng(1))
        b = tr(arr, np.random.default_rng(2))
        assert a.shape == b.shape == (96, 96, 3)
        assert not np.array_equal(a, b)        # random crop/flip applied

        t = create_transform(224, is_training=False, tf_preprocessing=True)
        assert isinstance(t, TfPreprocessTransform)

        # the pure-numpy resampler must match TF2 resize semantics —
        # jax.image.resize (same half-pixel/Keys-bicubic definition) is
        # the available oracle
        import jax
        from deepfake_detection_tpu.data.tf_preprocessing import _resize
        src = rng.integers(0, 256, (57, 41, 3)).astype(np.uint8)
        for method in ("bicubic", "bilinear"):
            ours = _resize(src, 32, method)
            oracle = np.asarray(jax.image.resize(
                src.astype(np.float32), (32, 32, 3), method=method,
                antialias=False))
            np.testing.assert_allclose(ours, oracle, atol=1e-2)

    def test_dataset_tar(self, tmp_path):
        """DatasetTar (reference dataset.py:602-630): classes from member
        dirnames sorted naturally; thread-safe reads; transform+rng path."""
        import tarfile
        from concurrent.futures import ThreadPoolExecutor
        from deepfake_detection_tpu.data import DatasetTar
        src = tmp_path / "src"
        for cls, color in (("class10", 10), ("class2", 200)):
            (src / cls).mkdir(parents=True)
            for i in range(3):
                Image.new("RGB", (32, 32), (color, i, 0)).save(
                    src / cls / f"{i}.jpg")
        tar_path = str(tmp_path / "data.tar")
        with tarfile.open(tar_path, "w") as tf:
            tf.add(src, arcname=".")
        ds = DatasetTar(tar_path)
        assert len(ds) == 6
        # natural sort: class2 before class10
        assert ds.class_to_idx == {"class2": 0, "class10": 1}
        img, y = ds[0]
        assert y in (0, 1) and img.size == (32, 32)
        # all labels present; concurrent reads from threads are safe
        with ThreadPoolExecutor(4) as ex:
            ys = sorted(y for _, y in ex.map(ds.__getitem__, range(6)))
        assert ys == [0, 0, 0, 1, 1, 1]
        # transform receives the per-sample rng
        ds.set_transform(lambda im, rng: np.asarray(im, np.uint8))
        img, _ = ds[1]
        assert isinstance(img, np.ndarray)

    def test_concat_dataset(self, tmp_path):
        from deepfake_detection_tpu.data import (ConcatDataset,
                                                 SyntheticDataset)
        a = SyntheticDataset(3, (8, 8, 3), seed=0)
        b = SyntheticDataset(5, (8, 8, 3), seed=1)
        ds = ConcatDataset([a, b])
        assert len(ds) == 8
        xa, _ = ds[2]
        np.testing.assert_array_equal(xa, a[2][0])
        xb, _ = ds[3]
        np.testing.assert_array_equal(xb, b[0][0])
        xn, _ = ds[-1]
        np.testing.assert_array_equal(xn, b[4][0])
        ds.set_epoch(3)
        assert a.epoch == b.epoch == 3

    def test_packed_frames_skip_concat_copy(self):
        """The native warp pre-packs frames into one (H, W, 12) buffer;
        MultiToNumpy/MultiConcate must pass it through copy-free unless a
        later transform replaced a frame."""
        from deepfake_detection_tpu.data import native
        from deepfake_detection_tpu.data.transforms import (
            MultiBlur, MultiConcate, MultiFusedGeometric, MultiToNumpy,
            PackedFrames)
        if not native.available():
            pytest.skip("native library unavailable")
        g = np.add.outer(np.arange(80), np.arange(80)) % 256
        img = Image.fromarray(np.stack([g] * 3, -1).astype(np.uint8))
        rng = np.random.default_rng(0)
        frames = MultiFusedGeometric(64)([img] * 4, rng)
        assert isinstance(frames, PackedFrames)
        out = MultiConcate()(MultiToNumpy()(frames, rng), rng)
        assert out is frames.base and out.shape == (64, 64, 12)
        # blur that fires voids the shortcut but still yields a clip
        blurred = MultiBlur(1.0, 1.0)(frames, rng)
        out2 = MultiConcate()(MultiToNumpy()(blurred, rng), rng)
        assert out2 is not frames.base and out2.shape == (64, 64, 12)
        # blur that does NOT fire keeps the packed identity
        same = MultiBlur(0.0, 1.0)(frames, rng)
        assert same is frames

    @pytest.mark.parametrize("native_path", [True, False])
    def test_fused_geometric_matches_sequential_chain(self, native_path,
                                                      monkeypatch):
        """MultiFusedGeometric (one warp) vs the reference-exact sequential
        rotate/flip/resize/crop chain: same rng draws, same geometry — mean
        pixel diff is resampling noise only.  Parametrized over BOTH warp
        backends: the C kernel and the PIL Image.transform fallback (whose
        index→continuous coefficient conversion a native-only run would
        never execute)."""
        if not native_path:
            monkeypatch.setenv("DFD_NO_NATIVE_DECODE", "1")
        from deepfake_detection_tpu.data.transforms import (
            MultiFusedGeometric, MultiRandomCrop,
            MultiRandomHorizontalFlip, MultiRandomResize, MultiRotate)

        def sequential(imgs, rng, size, rot):
            if rot:
                imgs = MultiRotate(rot)(imgs, rng)
            imgs = MultiRandomHorizontalFlip()(imgs, rng)
            imgs = MultiRandomResize(scale=(2 / 3, 3 / 2))(imgs, rng)
            return MultiRandomCrop(size, pad_if_needed=True)(imgs, rng)

        fused = MultiFusedGeometric(96, rotate_range=5)
        # odd extents included: PIL's expand-rotate canvas math shifts by
        # 1 px for odd sizes, and the crop-draw bounds must match exactly
        for w, h in ((160, 160), (141, 141), (155, 133)):
            g = np.add.outer(np.arange(h), np.arange(w)) % 256
            img = Image.fromarray(np.stack([g, (g + 40) % 256,
                                            (g + 80) % 256],
                                           -1).astype(np.uint8))
            for seed in range(6):
                a = np.asarray(
                    sequential([img], np.random.default_rng(seed), 96,
                               5)[0], np.float32)
                b = np.asarray(
                    fused([img], np.random.default_rng(seed))[0],
                    np.float32)
                assert a.shape == b.shape == (96, 96, 3)
                # same crop geometry ⇒ only resampling noise; a wrong
                # window, canvas size, or sign flip would push this to
                # tens of gray levels
                assert np.abs(a - b).mean() < 2.0, (w, h, seed)

    @pytest.mark.parametrize("native_path", [True, False])
    def test_fused_geometric_identity_params_exact(self, native_path,
                                                   monkeypatch):
        """With rotate 0 and scale pinned to 1 the fused warp degenerates to
        flip+crop and must be pixel-exact vs the sequential chain (both
        warp backends)."""
        if not native_path:
            monkeypatch.setenv("DFD_NO_NATIVE_DECODE", "1")
        from deepfake_detection_tpu.data.transforms import (
            MultiFusedGeometric, MultiRandomCrop,
            MultiRandomHorizontalFlip, MultiRandomResize)
        g = np.add.outer(np.arange(140), np.arange(150)) % 256
        img = Image.fromarray(np.stack([g, g, g], -1).astype(np.uint8))
        fused = MultiFusedGeometric(64, rotate_range=0, scale=(1.0, 1.0))
        for seed in range(4):
            rng = np.random.default_rng(seed)
            a = MultiRandomHorizontalFlip()([img], rng)
            a = MultiRandomResize(scale=(1.0, 1.0))(a, rng)
            a = MultiRandomCrop(64, pad_if_needed=True)(a, rng)
            b = fused([img], np.random.default_rng(seed))
            np.testing.assert_array_equal(np.asarray(a[0]),
                                          np.asarray(b[0]))

    def test_device_color_jitter_semantics(self):
        """Device jitter ops match PIL's ImageEnhance chain: replicate the
        factor draw from the key, apply PIL with the same factor, compare."""
        import jax
        import jax.numpy as jnp
        from PIL import ImageEnhance
        from deepfake_detection_tpu.data.device_augment import \
            make_device_color_jitter

        rng = np.random.default_rng(0)
        frame = rng.integers(0, 256, (24, 24, 3)).astype(np.uint8)
        x = np.concatenate([frame] * 4, -1)[None].astype(np.float32)

        # brightness-only: replicate the b draw from the split key
        fn = make_device_color_jitter((0.4, 0.0, 0.0), 0.0, 4)
        key = jax.random.PRNGKey(7)
        out = np.asarray(fn(jnp.asarray(x), key))
        skey = jax.random.split(key, 1)[0]
        kb = jax.random.split(skey, 5)[0]
        b = float(jax.random.uniform(kb, (), minval=0.6, maxval=1.4))
        pil = np.asarray(ImageEnhance.Brightness(
            Image.fromarray(frame)).enhance(b), np.float32)
        got = out[0, :, :, :3]
        # PIL rounds to uint8; device stays float — within 1 level
        assert np.abs(got - pil).max() <= 1.0, np.abs(got - pil).max()

        # flicker=1 blacks out every frame
        fn = make_device_color_jitter(None, 1.0, 4)
        out = np.asarray(fn(jnp.asarray(x), key))
        assert np.all(out == 0)

        # degenerate ranges are the identity
        fn = make_device_color_jitter((0.0, 0.0, 0.0), 0.0, 4)
        out = np.asarray(fn(jnp.asarray(x), key))
        np.testing.assert_allclose(out, x, atol=1e-3)

    def test_device_color_jitter_full_chain_vs_pil(self):
        """All three ops active: device output equals the PIL ImageEnhance
        chain applied in the SAME (replicated) order with the SAME factors
        — catches order-application and contrast-mean bugs the
        brightness-only test cannot."""
        import jax
        import jax.numpy as jnp
        from PIL import ImageEnhance
        from deepfake_detection_tpu.data.device_augment import \
            make_device_color_jitter

        rng = np.random.default_rng(3)
        frame = rng.integers(0, 256, (16, 16, 3)).astype(np.uint8)
        x = np.concatenate([frame] * 4, -1)[None].astype(np.float32)
        fn = make_device_color_jitter((0.4, 0.4, 0.4), 0.0, 4)
        key = jax.random.PRNGKey(11)
        out = np.asarray(fn(jnp.asarray(x), key))[0, :, :, :3]

        # replicate the draws exactly as device_augment does
        skey = jax.random.split(key, 1)[0]
        kb, kc, ks, kord, _ = jax.random.split(skey, 5)
        b = float(jax.random.uniform(kb, (), minval=0.6, maxval=1.4))
        c = float(jax.random.uniform(kc, (), minval=0.6, maxval=1.4))
        s = float(jax.random.uniform(ks, (), minval=0.6, maxval=1.4))
        order = np.asarray(jax.random.permutation(kord, 3))
        img = Image.fromarray(frame)
        for op in order:
            if op == 0:
                img = ImageEnhance.Brightness(img).enhance(b)
            elif op == 1:
                img = ImageEnhance.Contrast(img).enhance(c)
            else:
                img = ImageEnhance.Color(img).enhance(s)
        pil = np.asarray(img, np.float32)
        # PIL rounds to uint8 after each op; device stays float between
        # clamps — a few gray levels of accumulated rounding drift
        assert np.abs(out - pil).max() <= 4.0, np.abs(out - pil).max()

    def test_loader_device_jitter_e2e(self, tmp_path):
        """Train loader with device jitter (default): output is finite,
        correctly shaped, and differs from the jitter-free pipeline."""
        from deepfake_detection_tpu.data import create_deepfake_loader_v3
        root = str(tmp_path / "d")
        _make_v3_tree(root, n_real=2, n_fake=2)

        def batch(device_jitter, cj):
            ds = DeepFakeClipDataset(root)
            loader = create_deepfake_loader_v3(
                ds, (12, 32, 32), 2, is_training=True, num_workers=0,
                dtype=np.float32, color_jitter=cj,
                device_color_jitter=device_jitter)
            x, *_ = next(iter(loader))
            return np.asarray(x)

        a = batch(True, 0.4)
        assert a.shape == (2, 32, 32, 12) and np.isfinite(a).all()
        b = batch(True, None)
        assert not np.array_equal(a, b)     # jitter actually applied

    def test_eval_crop_center_deterministic(self, tmp_path):
        """--eval-crop center: identical pixels across epochs; the parity
        default (random) draws a fresh window per (epoch, index)."""
        from deepfake_detection_tpu.data import create_deepfake_loader_v3
        root = str(tmp_path / "d")
        _make_v3_tree(root, n_real=2, n_fake=2)
        # gradient frames, larger than the 32² crop, so the window matters
        grad = np.add.outer(np.arange(48), np.arange(48)) % 256
        img = Image.fromarray(np.stack([grad] * 3, -1).astype(np.uint8))
        for kind in ("real", "fake"):
            for d in os.listdir(os.path.join(root, kind)):
                for f in os.listdir(os.path.join(root, kind, d)):
                    img.save(os.path.join(root, kind, d, f))

        def first_batch(crop, epoch):
            ds = DeepFakeClipDataset(root)
            loader = create_deepfake_loader_v3(
                ds, (12, 32, 32), 2, is_training=False, num_workers=0,
                dtype=np.float32, eval_crop=crop)
            loader.set_epoch(epoch)     # drives the (seed, epoch, idx) rng
            x, *_ = next(iter(loader))
            return np.asarray(x)

        np.testing.assert_array_equal(first_batch("center", 0),
                                      first_batch("center", 7))
        assert not np.array_equal(first_batch("random", 0),
                                  first_batch("random", 7))

    def test_multi_root_colon_split(self, tmp_path):
        """'rootA:rootB' concatenates both trees, every clip path resolving
        under its own root (reference train.py:422 multi-root data-dir)."""
        ra, rb = str(tmp_path / "a"), str(tmp_path / "b")
        _make_v3_tree(ra, n_real=2, n_fake=3)
        _make_v3_tree(rb, n_real=4, n_fake=1)
        ds = DeepFakeClipDataset(f"{ra}:{rb}")
        single = [DeepFakeClipDataset(ra), DeepFakeClipDataset(rb)]
        assert len(ds) == len(single[0]) + len(single[1]) == (3+2) + (1+4)
        # every sample loads, and its paths live under the right root
        roots_seen = set()
        for i in range(len(ds)):
            paths, y = ds.sample_paths(i)
            root = ra if paths[0].startswith(ra) else rb
            assert all(p.startswith(root) for p in paths)
            roots_seen.add(root)
            img, _ = ds[i]                     # frames actually decode
        assert roots_seen == {ra, rb}
        # trailing/empty segments are tolerated
        assert len(DeepFakeClipDataset(f"{ra}:")) == len(single[0])


# ---------------------------------------------------------------------------
# Samplers
# ---------------------------------------------------------------------------

class TestSamplers:
    def test_train_shard_partition(self):
        samplers = [ShardedTrainSampler(103, num_shards=4, shard_index=i,
                                        batch_size=2, seed=1)
                    for i in range(4)]
        all_idx = np.concatenate([s.local_indices() for s in samplers])
        assert len(all_idx) == (103 // 8) * 8
        assert len(set(all_idx.tolist())) == len(all_idx)  # disjoint

    def test_train_epoch_reshuffle(self):
        s = ShardedTrainSampler(50, batch_size=5, seed=1)
        a = s.local_indices().copy()
        s.set_epoch(1)
        b = s.local_indices()
        assert not np.array_equal(a, b)

    def test_eval_padding_and_mask(self):
        samplers = [OrderedShardedSampler(10, num_shards=4, shard_index=i,
                                          batch_size=2) for i in range(4)]
        idx = np.concatenate([s.local_indices()[0] for s in samplers])
        valid = np.concatenate([s.local_indices()[1] for s in samplers])
        assert len(idx) == 16                      # padded to 4*2*2
        assert valid.sum() == 10                   # exactly dataset_len valid
        assert set(idx[valid].tolist()) == set(range(10))


# ---------------------------------------------------------------------------
# Mixup / collate
# ---------------------------------------------------------------------------

class TestMixup:
    def test_fast_collate(self):
        samples = [(np.full((8, 8, 12), i, np.uint8), i % 2)
                   for i in range(4)]
        imgs, tgts = fast_collate(samples)
        assert imgs.shape == (4, 8, 8, 12) and imgs.dtype == np.uint8
        assert tgts.tolist() == [0, 1, 0, 1]

    def test_collate_mixup_soft_targets(self):
        m = FastCollateMixup(mixup_alpha=1.0, label_smoothing=0.1,
                             num_classes=2)
        imgs = np.stack([np.zeros((4, 4, 3), np.uint8),
                         np.full((4, 4, 3), 200, np.uint8)])
        tgts = np.array([0, 1])
        out, soft = m(imgs, tgts, _rng(3))
        assert soft.shape == (2, 2)
        np.testing.assert_allclose(soft.sum(-1), 1.0, atol=1e-5)
        assert out.dtype == np.uint8

    # -- the tiled blend (PR 25) against the whole-batch expression -------

    _SHAPES = [(2, 4, 4, 3), (3, 37, 5, 12), (5, 16, 16, 12),
               (4, 5, 300, 128),         # one row > a tile, and many rows
               (3, 600, 600, 12)]        # the flagship's batch
    _LAMS = ["beta_near_0", "beta_near_1", 0.5, 0.25]   # .5 and .25: ties

    @staticmethod
    def _oracle(images, lam):
        """The blend as it was before it was tiled, kept as the oracle."""
        mixed = images.astype(np.float32) * lam + \
            images[::-1].astype(np.float32) * (1.0 - lam)
        np.round(mixed, out=mixed)
        return mixed.astype(np.uint8)

    @staticmethod
    def _lam(which):
        if not isinstance(which, str):
            return which
        draws = _rng(25).beta(0.1, 0.1, size=256)    # the flagship's alpha
        if which == "beta_near_0":
            return float(draws[draws < 0.05].max())
        return float(draws[draws > 0.95].min())

    @staticmethod
    def _batch(shape, seed=0):
        return _rng(seed).integers(0, 256, shape, dtype=np.uint8)

    @pytest.mark.parametrize("lam", _LAMS)
    @pytest.mark.parametrize("shape", _SHAPES, ids=str)
    def test_tiled_blend_bit_identical(self, shape, lam):
        imgs, lam = self._batch(shape), self._lam(lam)
        assert 0.0 < lam < 1.0
        want = self._oracle(imgs, lam)
        if lam in (0.5, 0.25):           # the case really holds ties
            exact = imgs.astype(np.float64) * lam + \
                imgs[::-1].astype(np.float64) * (1.0 - lam)
            assert (exact % 1.0 == 0.5).any()
        got = mixup_mod._blend_tiled(imgs, lam)
        assert got.dtype == np.uint8 and got.shape == imgs.shape
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("lam", _LAMS)
    @pytest.mark.parametrize("shape", _SHAPES[:3], ids=str)
    def test_tiled_blend_many_tiles(self, shape, lam, monkeypatch):
        """A tile far smaller than the batch: several tiles, the last one
        partial where H is no multiple of the tile's rows (37 = 7 x 5 + 2)."""
        monkeypatch.setattr(mixup_mod, "_TILE_ELEMS", 1024)
        imgs, lam = self._batch(shape, seed=1), self._lam(lam)
        np.testing.assert_array_equal(mixup_mod._blend_tiled(imgs, lam),
                                      self._oracle(imgs, lam))

    @pytest.mark.parametrize("view", ["batch_strided", "channel_slice",
                                      "row_strided", "transposed"])
    def test_tiled_blend_strided_input(self, view):
        """The shm ring hands in a view of a slab; any strides must do."""
        slab = self._batch((6, 40, 18, 12), seed=2)
        imgs = {"batch_strided": slab[::2],
                "channel_slice": slab[:3, :, :, 2:11],
                "row_strided": slab[:3, ::3],
                "transposed": slab[:3].transpose(0, 2, 1, 3)}[view]
        assert not imgs.flags["C_CONTIGUOUS"]
        before = slab.copy()
        lam = self._lam("beta_near_0")
        got = mixup_mod._blend_tiled(imgs, lam)
        np.testing.assert_array_equal(
            got, self._oracle(np.ascontiguousarray(imgs), lam))
        assert got.flags["C_CONTIGUOUS"]
        assert not np.shares_memory(got, slab)
        np.testing.assert_array_equal(slab, before)      # input only read

    @pytest.mark.parametrize("shape", [(1, 8, 8, 3), (3, 1, 7, 12),
                                       (0, 8, 8, 3), (2, 0, 8, 3)], ids=str)
    def test_tiled_blend_degenerate_shapes(self, shape):
        imgs = self._batch(shape, seed=3)
        np.testing.assert_array_equal(mixup_mod._blend_tiled(imgs, 0.3),
                                      self._oracle(imgs, 0.3))

    @pytest.mark.parametrize("seed", [3, 4, 5])
    def test_collate_mixup_is_the_oracle_on_its_own_draw(self, seed):
        """Through __call__: the draw, the soft targets and the blend, with
        a fresh output that shares nothing with the (unchanged) input."""
        m = FastCollateMixup(mixup_alpha=0.1, label_smoothing=0.1,
                             num_classes=2)
        imgs, tgts = self._batch((5, 16, 16, 12), seed), np.arange(5) % 2
        kept = imgs.copy()
        lam = float(_rng(seed).beta(0.1, 0.1))
        out, soft = m(imgs, tgts, _rng(seed))
        np.testing.assert_array_equal(out, self._oracle(kept, lam))
        np.testing.assert_array_equal(
            soft, mixup_mod.mixup_target_np(tgts, 2, lam, 0.1))
        np.testing.assert_array_equal(imgs, kept)
        assert out is not imgs and not np.shares_memory(out, imgs)
        assert vars(m) == vars(FastCollateMixup(0.1, 0.1, 2))   # stateless

    @pytest.mark.parametrize("how", ["blend_off", "lam_one"])
    def test_collate_mixup_early_returns_the_input_itself(self, how):
        m = FastCollateMixup(mixup_alpha=0.1, label_smoothing=0.1,
                             num_classes=2, blend=(how != "blend_off"))
        m.mixup_enabled = how != "lam_one"           # lam stays 1.0
        imgs = self._batch((4, 8, 8, 3))
        out, soft = m(imgs, np.arange(4) % 2, _rng(6))
        assert out is imgs
        assert soft.shape == (4, 2)


# ---------------------------------------------------------------------------
# RandomErasing (device)
# ---------------------------------------------------------------------------

class TestRandomErasing:
    def test_erase_const(self):
        import jax
        x = np.ones((2, 32, 32, 6), np.float32)
        out = random_erasing(jax.random.PRNGKey(0), x, probability=1.0,
                             min_area=0.1, max_area=0.3, img_num=2)
        out = np.asarray(out)
        assert out.shape == x.shape
        assert (out == 0).any()          # something was erased
        # frames erased independently: zero masks differ between frame slices
        z0 = (out[..., :3] == 0).sum()
        z1 = (out[..., 3:] == 0).sum()
        assert z0 > 0 and z1 > 0

    def test_no_erase_when_prob_zero(self):
        import jax
        x = np.ones((1, 16, 16, 3), np.float32)
        out = np.asarray(random_erasing(jax.random.PRNGKey(0), x,
                                        probability=0.0))
        np.testing.assert_array_equal(out, x)

    def test_aug_split_skips_clean(self):
        import jax
        x = np.ones((4, 32, 32, 3), np.float32)
        out = np.asarray(random_erasing(
            jax.random.PRNGKey(1), x, probability=1.0, min_area=0.2,
            max_area=0.4, num_splits=2))
        assert (out[:2] == 1).all()      # clean split untouched
        assert (out[2:] == 0).any()


# ---------------------------------------------------------------------------
# Loader end-to-end
# ---------------------------------------------------------------------------

class TestLoader:
    def test_synthetic_end_to_end(self):
        import jax.numpy as jnp
        ds = SyntheticDataset(length=16, image_shape=(64, 64, 12))
        loader = create_deepfake_loader_v3(
            ds, (12, 64, 64), batch_size=4, is_training=True, re_prob=0.2,
            re_max=0.05, num_workers=2, rotate_range=5, flicker=0.05,
            dtype=jnp.float32)
        batches = list(iter(loader))
        assert len(batches) == 4
        x, y = batches[0]
        assert x.shape == (4, 64, 64, 12)
        assert x.dtype == jnp.float32
        assert abs(float(x.mean())) < 3.0  # roughly normalized

    def test_eval_loader_mask(self):
        import jax.numpy as jnp
        ds = SyntheticDataset(length=10, image_shape=(32, 32, 12))
        loader = create_deepfake_loader_v3(
            ds, (12, 32, 32), batch_size=4, is_training=False,
            distributed=False, num_workers=1, dtype=jnp.float32)
        total_valid = 0
        for x, y, valid in loader:
            assert x.shape[0] == 4
            total_valid += int(np.asarray(valid).sum())
        assert total_valid == 10

    def test_determinism_across_worker_counts(self):
        import jax.numpy as jnp
        ds1 = SyntheticDataset(length=8, image_shape=(32, 32, 12))
        ds2 = SyntheticDataset(length=8, image_shape=(32, 32, 12))
        mk = lambda ds, w: create_deepfake_loader_v3(
            ds, (12, 32, 32), batch_size=4, is_training=True,
            num_workers=w, dtype=jnp.float32, re_prob=0.0)
        b1 = [np.asarray(x) for x, _ in mk(ds1, 1)]
        b2 = [np.asarray(x) for x, _ in mk(ds2, 4)]
        for a, b in zip(b1, b2):
            np.testing.assert_array_equal(a, b)


class TestCreateLoader:
    """Generic single-image loader factory (reference loader.py:372-456)."""

    def _folder(self, tmp_path, per_class=6, size=80):
        g = _rng(7)
        for c in ("cat", "dog"):
            d = tmp_path / "imgs" / c
            d.mkdir(parents=True)
            for i in range(per_class):
                Image.fromarray(g.integers(0, 255, (size, size, 3),
                                           dtype=np.uint8)).save(
                    d / f"{i}.jpg")
        from deepfake_detection_tpu.data import FolderDataset
        return FolderDataset(str(tmp_path / "imgs"))

    def test_train_end_to_end(self, tmp_path):
        import jax.numpy as jnp
        from deepfake_detection_tpu.data import create_loader
        ds = self._folder(tmp_path)
        loader = create_loader(ds, (3, 64, 64), batch_size=4,
                               is_training=True, re_prob=0.2,
                               color_jitter=0.4, num_workers=2,
                               dtype=jnp.float32)
        batches = list(iter(loader))
        assert len(batches) == 3
        x, y = batches[0]
        assert x.shape == (4, 64, 64, 3) and x.dtype == jnp.float32
        assert abs(float(x.mean())) < 3.0  # roughly normalized
        assert set(np.asarray(y).tolist()) <= {0, 1}

    def test_eval_mask_exact_count(self, tmp_path):
        import jax.numpy as jnp
        from deepfake_detection_tpu.data import create_loader
        ds = self._folder(tmp_path, per_class=5)   # 10 images, batch 4
        loader = create_loader(ds, (3, 64, 64), batch_size=4,
                               is_training=False, dtype=jnp.float32)
        total = 0
        for x, y, valid in loader:
            assert x.shape == (4, 64, 64, 3)
            total += int(np.asarray(valid).sum())
        assert total == 10

    def test_auto_augment_path(self, tmp_path):
        import jax.numpy as jnp
        from deepfake_detection_tpu.data import create_loader
        ds = self._folder(tmp_path, per_class=2)
        loader = create_loader(ds, (3, 32, 32), batch_size=2,
                               is_training=True, auto_augment="rand-m9-n2",
                               num_workers=1, dtype=jnp.float32)
        x, y = next(iter(loader))
        assert x.shape == (2, 32, 32, 3)

    def test_determinism_across_worker_counts(self, tmp_path):
        import jax.numpy as jnp
        from deepfake_detection_tpu.data import create_loader
        mk = lambda w: create_loader(
            self._folder(tmp_path / str(w)), (3, 48, 48), batch_size=4,
            is_training=True, num_workers=w, dtype=jnp.float32)
        b1 = [np.asarray(x) for x, _ in mk(1)]
        b2 = [np.asarray(x) for x, _ in mk(4)]
        assert b1 and len(b1) == len(b2)
        for a, b in zip(b1, b2):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# AutoAugment family
# ---------------------------------------------------------------------------

class TestAutoAugment:
    def test_autoaugment(self):
        tf = auto_augment_transform("original-mstd0.5", {})
        img = _frames(1, size=(64, 64))[0]
        out = tf(img, _rng(0))
        assert out.size == (64, 64)

    def test_randaugment(self):
        tf = rand_augment_transform("rand-m9-mstd0.5-inc1",
                                    {"translate_const": 20})
        img = _frames(1, size=(64, 64))[0]
        out = tf(img, _rng(0))
        assert out.size == (64, 64)
        # determinism
        a = np.asarray(tf(img, _rng(5)))
        b = np.asarray(tf(img, _rng(5)))
        np.testing.assert_array_equal(a, b)

    def test_augmix(self):
        tf = augment_and_mix_transform("augmix-m3-w3", {})
        img = _frames(1, size=(48, 48))[0]
        out = tf(img, _rng(0))
        assert out.size == (48, 48)


# ---------------------------------------------------------------------------
# Data config resolver
# ---------------------------------------------------------------------------

class TestResolveDataConfig:
    def test_v2_string_priority(self):
        cfg = resolve_data_config({"input_size_v2": "12,600,600",
                                   "input_size": (3, 224, 224)},
                                  verbose=False)
        assert cfg["input_size"] == (12, 600, 600)

    def test_model_mean_selection(self):
        cfg = resolve_data_config({"model": "xception"}, verbose=False)
        assert cfg["mean"] == (0.5, 0.5, 0.5)
        cfg = resolve_data_config({"model": "efficientnet_b0"}, verbose=False)
        assert cfg["mean"] == (0.485, 0.456, 0.406)

    def test_default_cfg_fallthrough(self):
        cfg = resolve_data_config(
            {}, default_cfg={"input_size": (3, 299, 299),
                             "interpolation": "bicubic", "crop_pct": 0.9},
            verbose=False)
        assert cfg["input_size"] == (3, 299, 299)
        assert cfg["crop_pct"] == 0.9


class TestCodeReviewRegressions:
    def test_autoaugment_originalr(self):
        tf = auto_augment_transform("originalr-mstd0.5", {})
        img = _frames(1, size=(64, 64))[0]
        assert tf(img, _rng(0)).size == (64, 64)

    def test_augmix_non_square(self):
        tf = augment_and_mix_transform("augmix-m3-w3", {})
        img = _frames(1, size=(64, 48))[0]  # W=64, H=48
        out = tf(img, _rng(0))
        assert out.size == (64, 48)

    def test_loader_abandoned_iteration_no_deadlock(self):
        import threading
        ds = SyntheticDataset(length=32, image_shape=(16, 16, 12))
        from deepfake_detection_tpu.data.loader import HostLoader
        from deepfake_detection_tpu.data.samplers import ShardedTrainSampler
        host = HostLoader(ds, ShardedTrainSampler(32, batch_size=4),
                          batch_size=4, num_workers=2, prefetch_depth=1)
        before = threading.active_count()
        for _ in range(3):
            it = iter(host)
            next(it)
            it.close()  # abandon mid-iteration
        import time
        time.sleep(1.0)
        assert threading.active_count() <= before + 2  # producers drained


class TestAugMix:
    def test_augmix_dataset_views(self):
        from deepfake_detection_tpu.data import SyntheticDataset
        from deepfake_detection_tpu.data.dataset import AugMixDataset
        base = SyntheticDataset(8, (32, 32, 12), 2, seed=0)
        ds = AugMixDataset(base, num_splits=3)
        rng = np.random.default_rng(0)
        views, y = ds.__getitem__(0, rng=rng)
        assert views.shape == (3, 32, 32, 12)
        clean, _ = base.__getitem__(0)
        np.testing.assert_array_equal(views[0], clean)   # split 0 is clean
        assert not np.array_equal(views[1], views[0])    # augmented differ
        assert not np.array_equal(views[2], views[1])

    def test_collate_split_major(self):
        from deepfake_detection_tpu.data.loader import fast_collate
        rng = np.random.default_rng(0)
        samples = [(rng.integers(0, 255, (3, 8, 8, 3), dtype=np.uint8), i)
                   for i in range(4)]
        images, targets = fast_collate(samples)
        assert images.shape == (12, 8, 8, 3)
        # split-major: first 4 are view 0 of each sample
        np.testing.assert_array_equal(images[0], samples[0][0][0])
        np.testing.assert_array_equal(images[4], samples[0][0][1])
        np.testing.assert_array_equal(targets, [0, 1, 2, 3] * 3)

    def test_loader_jsd_batch_shape(self):
        """VERDICT r2 #8 'done' criterion: batch leading dim is splits x B."""
        import jax.numpy as jnp
        from deepfake_detection_tpu.data import (SyntheticDataset,
                                                 create_deepfake_loader_v3)
        ds = SyntheticDataset(8, (32, 32, 3), 2, seed=0)
        loader = create_deepfake_loader_v3(
            ds, (3, 32, 32), batch_size=2, is_training=True,
            num_aug_splits=3, num_workers=1, dtype=jnp.float32)
        x, y = next(iter(loader))
        assert x.shape == (6, 32, 32, 3)
        assert y.shape == (6,)

    @pytest.mark.slow
    def test_jsd_e2e_smoke(self, tmp_path, devices):
        from deepfake_detection_tpu.runners.train import launch_main
        out = launch_main([
            "--dataset", "synthetic", "--model", "mnasnet_small",
            "--model-version", "", "--input-size-v2", "3,32,32",
            "--batch-size", "1", "--epochs", "1", "--opt", "sgd",
            "--lr", "0.01", "--sched", "step", "--log-interval", "4",
            "--workers", "1", "--compute-dtype", "float32",
            "--aug-splits", "3", "--jsd", "--smoothing", "0.1",
            "--output", str(tmp_path / "out")])
        assert out["best_metric"] is not None
