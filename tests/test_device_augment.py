"""Device-side augmentation (--augment-device on) parity suite.

The contract under test (ISSUE 9, data/device_augment.py):

* **Geometric warp** — same parameter distribution and rng draw order as
  the host chain (the shared ``fused_geometric_params`` draw), pixel
  diff bounded at the documented resampling tolerance vs the native
  fixed-point warp (the ``test_fused_geometric_matches_sequential_chain``
  precedent); integer-coefficient affines (flip/crop/pad) BIT-exact.
* **Blur** — true separable Gaussian (sigma = radius) vs PIL's 3-pass
  extended-box approximation: tolerance-based by design, unblurred
  frames untouched.
* **Mixup** — bit-exact vs FastCollateMixup (split-scalar blend defeats
  fma contraction), lambda drawn from the identical per-batch stream.
* **Stream-position parity** — the host passthrough consumes exactly the
  draws the host chain would, so noise_fake labels and every later
  per-sample draw match between paths.
* **Composition** — thread AND shm transports bit-identical, packed
  cache rides the same memcpy path, mid-epoch ``fast_forward`` tails
  bit-identical (PR 3's resume contract), ``--stem-s2d`` folds into the
  same single jitted prologue.
"""

import os
import warnings

import numpy as np
import pytest
from PIL import Image, ImageFilter

from deepfake_detection_tpu.data import (DeepFakeClipDataset,
                                         FastCollateMixup,
                                         create_deepfake_loader_v3)
from deepfake_detection_tpu.data.device_augment import (DeviceAugmentSpec,
                                                        derive_mixup_lam,
                                                        device_mixup_blend,
                                                        make_device_blur,
                                                        make_device_geometric)
from deepfake_detection_tpu.data.loader import DeviceLoader, HostLoader
from deepfake_detection_tpu.data.samplers import ShardedTrainSampler
from deepfake_detection_tpu.data.transforms import (
    Compose, DeviceAugmentPassthrough, MultiBlur, MultiConcate,
    MultiFusedGeometric, MultiToNumpy, fused_geometric_params)

pytestmark = [pytest.mark.smoke, pytest.mark.device_augment]


def _make_tree(root, n_real=3, n_fake=3, size=48, frames=4):
    """Small uniform-resolution v3 frame tree (jpg, decode-deterministic)."""
    g = np.random.default_rng(5)
    lists = {"real": [], "fake": []}
    for kind, n in (("real", n_real), ("fake", n_fake)):
        for i in range(n):
            name = f"{kind}clip{i}"
            d = os.path.join(root, kind, name)
            os.makedirs(d, exist_ok=True)
            for j in range(frames):
                arr = g.integers(0, 256, (size, size, 3)).astype(np.uint8)
                Image.fromarray(arr).save(os.path.join(d, f"{j}.jpg"),
                                          quality=95)
            lists[kind].append(f"{name}:{frames}")
    for kind, lst in lists.items():
        with open(os.path.join(root, f"{kind}_list.txt"), "w") as f:
            f.write("\n".join(lst) + "\n")
    return root


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return _make_tree(str(tmp_path_factory.mktemp("davt") / "d"))


def _collect(loader, epoch=0):
    loader.set_epoch(epoch)
    out = [(np.asarray(b[0]), np.asarray(b[1])) for b in loader]
    loader.close()
    return out


def _factory_loader(ds, augment_device, *, mixup=True, seed=7, epoch=0,
                    rotate=5, blur=0.3, jitter=None, **kw):
    import jax.numpy as jnp
    cm = FastCollateMixup(0.5, 0.1, 2) if mixup else None
    return create_deepfake_loader_v3(
        ds, (12, 32, 32), 2, is_training=True, num_workers=kw.pop(
            "num_workers", 1),
        dtype=jnp.float32, color_jitter=jitter, rotate_range=rotate,
        blur_prob=blur, blur_radius=1, collate_mixup=cm,
        augment_device=augment_device, seed=seed, **kw)


# ---------------------------------------------------------------------------
# Unit: warp
# ---------------------------------------------------------------------------

class TestDeviceWarp:
    def test_matches_host_fused_warp_at_tolerance(self):
        """Random rotate/flip/resize/crop geometry: device float bilinear
        vs the host render (native fixed-point 8-bit weights, or the PIL
        fallback) — identical parameter draws by construction (one shared
        fused_geometric_params), so only resampling arithmetic differs."""
        spec = DeviceAugmentSpec(size=(32, 32), rotate_range=7, img_num=1)
        warp = make_device_geometric(spec)
        host = MultiFusedGeometric(32, rotate_range=7)
        g = np.add.outer(np.arange(47), np.arange(53)) % 256
        img = Image.fromarray(np.stack([g, (g + 60) % 256, (g + 120) % 256],
                                       -1).astype(np.uint8))
        for seed in range(8):
            ref = np.asarray(host([img], np.random.default_rng(seed))[0],
                             np.float32)
            coeffs = np.asarray([fused_geometric_params(
                53, 47, (32, 32), 7, (2 / 3, 3 / 2), 0.5,
                np.random.default_rng(seed))], np.float32)
            dev = np.asarray(warp(np.asarray(img, np.uint8)[None],
                                  coeffs))[0]
            # fixed-point vs float bilinear: ±1 LSB weights pre-round →
            # occasional off-by-one pixels, nothing structural
            d = np.abs(dev - ref)
            assert d.mean() < 0.5 and d.max() <= 2.0, (seed, d.mean(),
                                                       d.max())

    def test_integer_affine_bit_exact_incl_padding(self):
        """scale==1 / rotate==0 degenerates to flip+pad+crop: integer
        coefficients, exact f32 coords, bit-exact vs the host chain —
        including the pad_if_needed region (source smaller than crop)."""
        spec = DeviceAugmentSpec(size=(64, 64), rotate_range=0,
                                 scale=(1.0, 1.0), img_num=1)
        warp = make_device_geometric(spec)
        host = MultiFusedGeometric(64, rotate_range=0, scale=(1.0, 1.0))
        g = np.random.default_rng(3).integers(0, 256, (50, 40, 3)
                                              ).astype(np.uint8)
        img = Image.fromarray(g)
        for seed in range(6):
            ref = np.asarray(host([img], np.random.default_rng(seed))[0],
                             np.uint8)
            coeffs = np.asarray([fused_geometric_params(
                40, 50, (64, 64), 0, (1.0, 1.0), 0.5,
                np.random.default_rng(seed))], np.float32)
            dev = np.asarray(warp(g[None], coeffs))[0].astype(np.uint8)
            np.testing.assert_array_equal(dev, ref, err_msg=str(seed))


# ---------------------------------------------------------------------------
# Unit: blur
# ---------------------------------------------------------------------------

class TestDeviceBlur:
    def test_vs_pil_gaussian_tolerance(self):
        """True Gaussian (device) vs PIL's extended-box approximation:
        documented tolerance — tight on smooth content, bounded on
        adversarial uint8 noise (PIL's own approximation error)."""
        spec = DeviceAugmentSpec(size=(40, 40), blur_prob=1.0,
                                 blur_radius=1.0, img_num=1)
        blur = make_device_blur(spec)
        rng = np.random.default_rng(0)
        noise = rng.integers(0, 256, (40, 40, 3)).astype(np.uint8)
        grad = (np.add.outer(np.arange(40), np.arange(40)) * 2 % 256
                ).astype(np.uint8)[..., None].repeat(3, -1)
        mask = np.ones((1, 1), bool)
        for arr, mean_tol, max_tol in ((grad, 0.6, 4.0), (noise, 1.5, 16.0)):
            ref = np.asarray(Image.fromarray(arr).filter(
                ImageFilter.GaussianBlur(1.0)), np.float32)
            dev = np.asarray(blur(arr[None].astype(np.float32), mask))[0]
            d = np.abs(dev - ref)
            assert d.mean() < mean_tol and d.max() <= max_tol, \
                (d.mean(), d.max())

    def test_mask_selects_frames(self):
        """Only frames whose host coin fired blur; the rest pass through
        bit-identical (the bit-exact suite depends on this)."""
        spec = DeviceAugmentSpec(size=(16, 16), blur_prob=0.5,
                                 blur_radius=1.0, img_num=2)
        blur = make_device_blur(spec)
        x = np.random.default_rng(1).integers(
            0, 256, (1, 16, 16, 6)).astype(np.float32)
        out = np.asarray(blur(x, np.asarray([[False, True]])))
        np.testing.assert_array_equal(out[..., :3], x[..., :3])
        assert not np.array_equal(out[..., 3:], x[..., 3:])


# ---------------------------------------------------------------------------
# Unit: mixup
# ---------------------------------------------------------------------------

class TestDeviceMixup:
    def test_bit_exact_vs_collate_blend(self):
        """500 beta draws: the split-scalar device blend equals numpy's
        mul-round/add-round uint8 blend bit-for-bit (fma contraction made
        the naive formulation flip .5-boundary pixels)."""
        import jax.numpy as jnp
        x = np.random.default_rng(0).integers(
            0, 256, (8, 16, 16, 12)).astype(np.uint8)
        for seed in range(500):
            lam = float(np.random.default_rng(seed).beta(0.2, 0.2))
            host = x.astype(np.float32) * lam + \
                x[::-1].astype(np.float32) * (1.0 - lam)
            np.round(host, out=host)
            dev = np.asarray(device_mixup_blend(
                jnp.asarray(x, jnp.float32), jnp.float32(lam),
                jnp.float32(1.0 - lam)))
            np.testing.assert_array_equal(dev, host, err_msg=str(seed))

    def test_block_local_flip(self):
        """blocks=2 flips within each half — the per-process collate
        semantics the multi-host device blend must preserve."""
        import jax.numpy as jnp
        x = np.arange(4, dtype=np.float32).reshape(4, 1, 1, 1) * 10
        out = np.asarray(device_mixup_blend(
            jnp.asarray(x), jnp.float32(0.0), jnp.float32(1.0), blocks=2))
        np.testing.assert_array_equal(out.ravel(), [10, 0, 30, 20])

    def test_lam_stream_matches_collate(self):
        """derive_mixup_lam replays FastCollateMixup's exact per-batch
        generator (seed, epoch, batch, 0x77) and beta draw."""
        cm = FastCollateMixup(0.3, 0.1, 2)
        rng = np.random.default_rng(np.random.SeedSequence([7, 2, 5, 0x77]))
        imgs = np.zeros((2, 4, 4, 3), np.uint8)
        _, soft = cm(imgs, np.asarray([0, 1]), rng)
        lam, om = derive_mixup_lam(7, 2, 5, 0.3, True)
        expect = np.random.default_rng(np.random.SeedSequence(
            [7, 2, 5, 0x77])).beta(0.3, 0.3)
        assert lam == np.float32(expect) and om == np.float32(1.0 - expect)
        # disabled stream: lam pinned to 1 without a draw
        lam, om = derive_mixup_lam(7, 2, 5, 0.3, False)
        assert lam == 1.0 and om == 0.0


# ---------------------------------------------------------------------------
# Pipeline parity (factory level)
# ---------------------------------------------------------------------------

class TestPipelineParity:
    def test_full_chain_tolerance_and_targets_exact(self, tree):
        """Factory loaders, rotate+blur+mixup active: device output within
        the documented resampling tolerance of the host chain, soft
        targets identical (same lambda stream)."""
        off = _collect(_factory_loader(DeepFakeClipDataset(tree), False))
        on = _collect(_factory_loader(DeepFakeClipDataset(tree), True))
        assert len(off) == len(on) > 0
        for (xo, yo), (xn, yn) in zip(off, on):
            np.testing.assert_allclose(yo, yn, atol=1e-6)
            d = np.abs(xo - xn)          # normalized units (std ≈ 0.23·255)
            assert d.mean() < 0.02 and d.max() < 0.5, (d.mean(), d.max())

    def _manual_pair(self, tree, dev, *, noise_fake=False, backend="thread",
                     num_workers=1, seed=7):
        """Host-chain vs device-path loaders pinned to scale=(1,1)/rotate=0
        (integer affine) and blur off — the bit-exact configuration."""
        import jax.numpy as jnp
        ds = DeepFakeClipDataset(tree, noise_fake=noise_fake)
        scale = (1.0, 1.0)
        if dev:
            ds.set_transform(Compose([DeviceAugmentPassthrough(
                32, rotate_range=0, scale=scale, blur_prob=0.0)]))
        else:
            ds.set_transform(Compose([
                MultiFusedGeometric(32, rotate_range=0, scale=scale),
                MultiToNumpy(), MultiConcate()]))
        cm = FastCollateMixup(0.5, 0.1, 2, blend=not dev)
        sampler = ShardedTrainSampler(len(ds), batch_size=2, seed=seed)
        if backend == "shm":
            from deepfake_detection_tpu.data.shm_ring import ShmRingLoader
            host = ShmRingLoader(ds, sampler, 2, seed=seed,
                                 num_workers=num_workers, collate_mixup=cm)
        else:
            host = HostLoader(ds, sampler, 2, seed=seed,
                              num_workers=num_workers, collate_mixup=cm)
        spec = DeviceAugmentSpec(
            size=(32, 32), rotate_range=0, scale=scale, blur_prob=0.0,
            img_num=4, mixup=True, mixup_alpha=0.5) if dev else None
        return DeviceLoader(host, dtype=jnp.float32, img_num=4, seed=seed,
                            device_augment=spec)

    def test_flip_crop_mixup_bit_exact(self, tree):
        """The ISSUE's hard bit-exact claim: integer-affine geometry + the
        device mixup blend reproduce the host chain bit-for-bit, across
        epochs (bucket rotation included)."""
        for epoch in (0, 1):
            A = _collect(self._manual_pair(tree, False), epoch)
            B = _collect(self._manual_pair(tree, True), epoch)
            assert len(A) == len(B) > 0
            for (xa, ya), (xb, yb) in zip(A, B):
                np.testing.assert_array_equal(ya, yb)
                np.testing.assert_array_equal(xa, xb)

    def test_noise_fake_draw_order_pinned(self, tree):
        """noise_fake flips labels with the per-sample rng AFTER the
        transform: identical labels prove the passthrough consumed
        exactly the host chain's draw count."""
        A = _collect(self._manual_pair(tree, False, noise_fake=True))
        B = _collect(self._manual_pair(tree, True, noise_fake=True))
        for (_, ya), (_, yb) in zip(A, B):
            np.testing.assert_array_equal(ya, yb)

    def test_shm_transport_bit_identical(self, tree):
        """--loader-backend shm composes: spawned workers run the same
        passthrough (jax-free) and the consumer derives the same params —
        batches bit-identical to the thread transport."""
        A = _collect(self._manual_pair(tree, True, backend="thread"))
        B = _collect(self._manual_pair(tree, True, backend="shm",
                                       num_workers=2))
        assert len(A) == len(B) > 0
        for (xa, ya), (xb, yb) in zip(A, B):
            np.testing.assert_array_equal(ya, yb)
            np.testing.assert_array_equal(xa, xb)

    def test_packed_cache_composes_bit_identical(self, tree, tmp_path):
        """--data-packed + --augment-device: the mmap passthrough (the
        'host is a memcpy' steady state) yields batches bit-identical to
        the decode-path device augment at matching pack resolution."""
        from deepfake_detection_tpu.data.packed import (PackedDataset,
                                                        write_pack)
        pack = str(tmp_path / "pack")
        write_pack([tree], pack, image_size=48, frames_per_clip=4,
                   shard_size=8, workers=2)
        dec = _collect(_factory_loader(DeepFakeClipDataset(tree), True))
        pk = _collect(_factory_loader(
            PackedDataset(pack, roots=[tree]), True))
        assert len(dec) == len(pk) > 0
        for (xa, ya), (xb, yb) in zip(dec, pk):
            np.testing.assert_array_equal(ya, yb)
            np.testing.assert_array_equal(xa, xb)

    def test_fast_forward_tail_bit_identical(self, tree):
        """PR 3's resume contract survives: a fresh device-augment loader
        fast-forwarded to batch k yields the full epoch's tail
        bit-identically (params are pure functions of absolute
        position)."""
        full = _collect(_factory_loader(DeepFakeClipDataset(tree), True,
                                        epoch=1), epoch=1)
        lt = _factory_loader(DeepFakeClipDataset(tree), True)
        lt.set_epoch(1)
        lt.fast_forward(1)
        tail = [(np.asarray(x), np.asarray(y)) for x, y in lt]
        lt.close()
        assert len(tail) == len(full) - 1 > 0
        for (xa, ya), (xb, yb) in zip(full[1:], tail):
            np.testing.assert_array_equal(ya, yb)
            np.testing.assert_array_equal(xa, xb)

    def test_determinism_across_worker_counts(self, tree):
        A = _collect(_factory_loader(DeepFakeClipDataset(tree), True,
                                     num_workers=1))
        B = _collect(_factory_loader(DeepFakeClipDataset(tree), True,
                                     num_workers=4))
        for (xa, _), (xb, _) in zip(A, B):
            np.testing.assert_array_equal(xa, xb)


# ---------------------------------------------------------------------------
# s2d fold + single dispatch
# ---------------------------------------------------------------------------

class TestS2dFold:
    def test_s2d_layout_parity_in_unified_prologue(self, tree):
        """--stem-s2d folds into the SAME single jitted prologue after
        augment→normalize: its output equals space_to_depth applied to
        the non-s2d prologue output (layout parity with the two-stage
        path)."""
        from deepfake_detection_tpu.ops.conv import space_to_depth
        base = _collect(_factory_loader(DeepFakeClipDataset(tree), True))
        s2d = _collect(_factory_loader(DeepFakeClipDataset(tree), True,
                                       stem_s2d=True))
        assert len(base) == len(s2d) > 0
        for (xa, _), (xb, _) in zip(base, s2d):
            ref = np.asarray(space_to_depth(xa))
            assert xb.shape == ref.shape == (2, 16, 16, 48)
            np.testing.assert_array_equal(xb, ref)

    def test_single_prologue_dispatch(self, tree):
        """The unified augment+normalize+s2d prologue is ONE compiled
        callable — iterating must not grow the jit cache past a single
        entry (single dispatch per batch)."""
        loader = _factory_loader(DeepFakeClipDataset(tree), True,
                                 stem_s2d=True)
        list(loader)
        loader.close()
        assert loader._prologue._cache_size() == 1


# ---------------------------------------------------------------------------
# Config / factory guard rails + satellites
# ---------------------------------------------------------------------------

class TestConfigAndFallbacks:
    def test_config_validation(self):
        from deepfake_detection_tpu.config import TrainConfig
        with pytest.raises(ValueError, match="augment_device"):
            TrainConfig(augment_device="maybe")
        with pytest.raises(ValueError, match="host-geom"):
            TrainConfig(augment_device="on", host_geom=True)
        with pytest.raises(ValueError, match="host-color-jitter"):
            TrainConfig(augment_device="on", host_color_jitter=True)
        TrainConfig(augment_device="on")      # valid

    def test_factory_host_jitter_conflict(self, tree):
        import jax.numpy as jnp
        with pytest.raises(ValueError, match="host"):
            create_deepfake_loader_v3(
                DeepFakeClipDataset(tree), (12, 32, 32), 2,
                is_training=True, dtype=jnp.float32, color_jitter=0.4,
                device_color_jitter=False, augment_device=True)

    def test_host_geom_conflict(self, tree):
        import jax.numpy as jnp
        with pytest.raises(ValueError, match="fused_geom"):
            create_deepfake_loader_v3(
                DeepFakeClipDataset(tree), (12, 32, 32), 2,
                is_training=True, dtype=jnp.float32, color_jitter=None,
                fused_geom=False, augment_device=True)

    def test_aug_splits_falls_back_to_host(self, tree, caplog):
        """AugMix aug-splits keep the host chain (logged, never silent):
        the loader still works and matches the augment-off path
        bit-for-bit."""
        import jax.numpy as jnp
        import logging

        def build(augdev):
            return create_deepfake_loader_v3(
                DeepFakeClipDataset(tree), (12, 32, 32), 2,
                is_training=True, num_workers=1, dtype=jnp.float32,
                color_jitter=None, num_aug_splits=2,
                augment_device=augdev, seed=7)
        with caplog.at_level(logging.INFO,
                             logger="deepfake_detection_tpu.data.loader"):
            on = build(True)
        assert not on.augment_device
        assert any("falls back" in r.message for r in caplog.records)
        A = _collect(build(False))
        B = _collect(on)
        for (xa, ya), (xb, yb) in zip(A, B):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)

    def test_nonuniform_source_raises(self):
        pt = DeviceAugmentPassthrough(32)
        frames = [np.zeros((40, 40, 3), np.uint8),
                  np.zeros((48, 40, 3), np.uint8)]
        with pytest.raises(ValueError, match="uniform source"):
            pt(frames, np.random.default_rng(0))

    def test_blur_radius_rename_aliases(self):
        from deepfake_detection_tpu.data.transforms_factory import \
            transforms_deepfake_train_v3
        with pytest.warns(DeprecationWarning):
            b = MultiBlur(0.5, blur_radiu=2.5)
        assert b.blur_radius == 2.5 and b.blur_radiu == 2.5
        assert MultiBlur(0.5, 2.5).blur_radius == 2.5
        with pytest.warns(DeprecationWarning):
            tf = transforms_deepfake_train_v3(32, blur_prob=0.5,
                                              blur_radiu=1.5)
        blur = [t for t in tf.transforms if isinstance(t, MultiBlur)][0]
        assert blur.blur_radius == 1.5
        # positional/keyword modern spelling, no warning
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            tf = transforms_deepfake_train_v3(32, blur_prob=0.5,
                                              blur_radius=1.5)

    def test_config_to_factory_wiring(self, tree):
        """config → factory: the runner's exact kwargs with
        --augment-device on yield a device-augment train loader, a plain
        eval loader, and a blend-elided collate mixup."""
        import jax.numpy as jnp
        from deepfake_detection_tpu.config import TrainConfig
        cfg = TrainConfig.from_args([
            "--data", tree, "--augment-device", "on", "--mixup", "0.1",
            "--rotate-range", "5", "--blur-prob", "0.3"])
        assert cfg.augment_device == "on"
        ds = DeepFakeClipDataset(tree)
        cm = FastCollateMixup(cfg.mixup, cfg.smoothing, cfg.num_classes)
        train_loader = create_deepfake_loader_v3(
            ds, (12, 32, 32), 2, is_training=True, collate_mixup=cm,
            color_jitter=cfg.color_jitter, rotate_range=cfg.rotate_range,
            blur_radius=1, blur_prob=cfg.blur_prob,
            device_color_jitter=not cfg.host_color_jitter,
            fused_geom=not cfg.host_geom,
            augment_device=cfg.augment_device == "on",
            dtype=jnp.float32, num_workers=1, seed=cfg.seed)
        assert train_loader.augment_device
        assert cm.blend is False         # blend elided, targets host-side
        assert train_loader._augment.mixup and \
            train_loader._augment.blur_prob == pytest.approx(0.3)
        train_loader.close()
        eval_loader = create_deepfake_loader_v3(
            DeepFakeClipDataset(tree), (12, 32, 32), 2, is_training=False,
            augment_device=cfg.augment_device == "on",
            dtype=jnp.float32, num_workers=1, seed=cfg.seed)
        assert not eval_loader.augment_device   # eval path untouched
        eval_loader.close()

    def test_telemetry_counters(self, tree):
        """loader_collector exposes the augment-path gauge and the
        elided-host-stages counter (satellite: obs attribution)."""
        from deepfake_detection_tpu.obs.telemetry import loader_collector
        loader = _factory_loader(DeepFakeClipDataset(tree), True)
        n = len(list(loader))
        out = loader_collector(loader)()
        loader.close()
        assert out["gauges"]["input_train_augment_path_device"] == 1.0
        # 3 clips/batch=2 → n batches x 2 samples x 3 stages (warp, blur,
        # mixup blend)
        assert out["counters"][
            "input_train_host_augment_stages_elided_total"] == n * 2 * 3
        off = _factory_loader(DeepFakeClipDataset(tree), False)
        list(off)
        out = loader_collector(off)()
        off.close()
        assert out["gauges"]["input_train_augment_path_device"] == 0.0
        assert out["counters"][
            "input_train_host_augment_stages_elided_total"] == 0


# ---------------------------------------------------------------------------
# e2e: SIGTERM kill + --auto-resume with --augment-device on (slow tier:
# three fresh-interpreter CLI runs, the test_chaos_e2e idiom/budget note)
# ---------------------------------------------------------------------------

_CLI_DRIVER = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
if cache:
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from deepfake_detection_tpu.runners.train import launch_main
out = launch_main(sys.argv[1:])
print("RESULT " + json.dumps({"best_metric": out["best_metric"]}))
"""

# rotate/blur/mixup all live on device; RandomErasing rides the same
# prologue key stream — bit-identity after resume proves every device-
# augment parameter stream (per-sample geometry/blur, per-batch lambda,
# per-step prologue key) fast-forwards to the absolute position
_E2E_BASE = ["--dataset", "synthetic", "--model", "vit_tiny_patch16_224",
             "--model-version", "", "--input-size-v2", "3,32,32",
             "--batch-size", "2", "--epochs", "2", "--opt", "adamw",
             "--lr", "1e-3", "--sched", "step", "--log-interval", "2",
             "--workers", "1", "--compute-dtype", "float32",
             "--reprob", "0.25", "--seed", "42",
             "--augment-device", "on", "--mixup", "0.2",
             "--rotate-range", "5", "--blur-prob", "0.3"]


def _launch_cli(args, chaos="", timeout=600):
    import subprocess
    import sys as _sys

    import jax
    env = dict(os.environ)
    env.pop("DFD_CHAOS", None)
    if chaos:
        env["DFD_CHAOS"] = chaos
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        jax.config.jax_compilation_cache_dir or "")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run([_sys.executable, "-c", _CLI_DRIVER, *args],
                          cwd=repo, env=env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.slow
@pytest.mark.chaos
def test_sigterm_resume_bit_identical_with_device_augment(tmp_path):
    """Acceptance pin: a SIGTERM-killed + --auto-resume run with
    --augment-device on ends bit-identical to the uninterrupted run."""
    import jax
    from deepfake_detection_tpu.train import load_checkpoint_file
    ref_out = tmp_path / "ref"
    r = _launch_cli(_E2E_BASE + ["--experiment", "ref",
                                 "--output", str(ref_out)])
    assert r.returncode == 0, \
        f"rc={r.returncode}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"

    out = tmp_path / "out"
    args = _E2E_BASE + ["--experiment", "run", "--output", str(out),
                        "--auto-resume"]
    r1 = _launch_cli(args, chaos="sigterm@11")    # mid-epoch-1 kill
    assert r1.returncode == 75, \
        f"rc={r1.returncode}\n{r1.stdout[-2000:]}\n{r1.stderr[-2000:]}"
    r2 = _launch_cli(args)
    assert r2.returncode == 0, \
        f"rc={r2.returncode}\n{r2.stdout[-2000:]}\n{r2.stderr[-2000:]}"
    assert "Auto-resumed" in r2.stderr + r2.stdout

    ref_sd, _ = load_checkpoint_file(str(ref_out / "ref" /
                                         "checkpoint-1.ckpt"))
    run_sd, _ = load_checkpoint_file(str(out / "run" / "checkpoint-1.ckpt"))
    la, lb = jax.tree.leaves(ref_sd), jax.tree.leaves(run_sd)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(xa), np.asarray(xb),
            err_msg="--augment-device on resume diverged from the "
                    "uninterrupted run")
