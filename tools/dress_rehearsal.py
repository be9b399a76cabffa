"""Flagship dress rehearsal: train through the REAL input pipeline on chip.

VERDICT r4 item 6: ``bench.py`` measures the flagship step on pre-staged
device tensors, so infeed + step + checkpoint have never run *together*
at the flagship shape.  This tool runs a short ``efficientnet_deepfake_v4``
train at 12x600x600 on synthetic JPEG clips through the full
``DeepFakeClipDataset -> create_deepfake_loader_v3 -> device prologue``
path (reference hot loop: dfd/runners/train.py:594-700), measuring:

  * steps/s and frames/s end-to-end (vs bench.py's device-only number);
  * host wait per step — time blocked in ``next(loader)``, i.e. the
    infeed shortfall the async double-buffer could not hide;
  * one mid-run async checkpoint save (cost visible in the step stream).

Writes one JSON line to stdout and ``DRESS_REHEARSAL.json`` at repo root.

CPU smoke: ``python tools/dress_rehearsal.py --model mnasnet_small
--size 64 --steps 6 --clips 8`` exercises the same path in seconds.
"""

from __future__ import annotations

import argparse
import atexit
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _log(msg: str) -> None:
    print(f"[dress] {msg}", file=sys.stderr, flush=True)


def make_clip_tree(root: str, n_clips: int, jpeg_size: int,
                   frames: int = 4) -> None:
    """Synthetic v3 list-file tree: gradient+noise JPEGs (realistic decode
    cost, unlike flat-color images that JPEG-compress to nothing)."""
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(0)
    base = np.add.outer(np.arange(jpeg_size), np.arange(jpeg_size))
    base = (base * 255.0 / base.max()).astype(np.float32)
    for kind, n in (("real", n_clips // 2), ("fake", n_clips - n_clips // 2)):
        lines = []
        for i in range(n):
            name = f"{kind}clip{i}"
            d = os.path.join(root, kind, name)
            os.makedirs(d, exist_ok=True)
            for j in range(frames):
                noise = rng.normal(0, 24, (jpeg_size, jpeg_size, 3))
                img = np.clip(base[..., None] + noise, 0, 255).astype("uint8")
                Image.fromarray(img).save(os.path.join(d, f"{j}.jpg"),
                                          quality=90)
            lines.append(f"{name}:{frames}")
        with open(os.path.join(root, f"{kind}_list.txt"), "w") as f:
            f.write("\n".join(lines) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="efficientnet_deepfake_v4")
    ap.add_argument("--size", type=int, default=600)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--clips", type=int, default=32)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--out", default=os.path.join(REPO, "DRESS_REHEARSAL.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from deepfake_detection_tpu.utils.compile_cache import \
        setup_compile_cache
    setup_compile_cache()
    import numpy as np

    from deepfake_detection_tpu.data import (DeepFakeClipDataset,
                                             create_deepfake_loader_v3)
    from deepfake_detection_tpu.losses import cross_entropy
    from deepfake_detection_tpu.models import create_model, init_model
    from deepfake_detection_tpu.optim import create_optimizer
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_train_step)
    from deepfake_detection_tpu.train.checkpoint import (save_checkpoint_file,
                                                         wait_pending_saves)
    from types import SimpleNamespace

    dev = jax.devices()[0]
    _log(f"device: {dev.device_kind}")

    tmp = tempfile.mkdtemp(prefix="dress_")

    def _cleanup() -> None:
        # flush the async checkpoint write before deleting its target dir
        try:
            wait_pending_saves()
        except Exception:  # noqa: BLE001 — cleanup must not mask the error
            pass
        shutil.rmtree(tmp, ignore_errors=True)

    atexit.register(_cleanup)
    # JPEGs 10% larger than the crop so RandomResizedCrop does real work
    jpeg_size = int(args.size * 1.1)
    _log(f"writing {args.clips} synthetic clips at {jpeg_size}^2 ...")
    t0 = time.perf_counter()
    make_clip_tree(tmp, args.clips, jpeg_size)
    _log(f"clip tree ready in {time.perf_counter() - t0:.1f}s")

    ds = DeepFakeClipDataset(tmp, is_training=True)
    chans = 12
    loader = create_deepfake_loader_v3(
        ds, (chans, args.size, args.size), args.batch, is_training=True,
        num_workers=args.workers, dtype=jnp.bfloat16, color_jitter=0.4,
        flicker=0.1, rotate_range=10, seed=42)

    _log("building + initializing model ...")
    extra = {"remat_policy": args.remat} if args.remat else {}
    model = create_model(args.model, num_classes=2, in_chans=chans,
                         dtype=jnp.bfloat16, **extra)
    variables = init_model(model, jax.random.PRNGKey(0),
                           (2, args.size, args.size, chans), training=True)
    cfg = SimpleNamespace(opt="rmsproptf", opt_eps=1e-8, momentum=0.9,
                          weight_decay=1e-5, lr=1.2e-5)
    tx = create_optimizer(cfg)
    state = create_train_state(variables, tx, with_ema=True)
    step = make_train_step(model, tx, cross_entropy, mesh=None,
                           bn_mode="global", ema_decay=0.9998)
    key = jax.random.PRNGKey(1)

    _log("warmup (compile + loader spin-up) ...")
    epoch, it = 0, None

    def next_batch():
        """Pull the next (x, y) pair, rolling epochs; returns host wait s."""
        nonlocal epoch, it
        t = time.perf_counter()
        while True:
            if it is None:
                loader.set_epoch(epoch)
                it = iter(loader)
            try:
                x, y, *_ = next(it)
                return x, y, time.perf_counter() - t
            except StopIteration:
                epoch += 1
                it = None

    x, y, _ = next_batch()
    t0 = time.perf_counter()
    state, metrics = step(state, x, y, key)
    jax.block_until_ready(metrics["loss"])
    compile_s = time.perf_counter() - t0
    _log(f"first step (compile) {compile_s:.1f}s; measuring {args.steps} "
         f"steps ...")

    waits, ckpt_s = [], None
    t0 = time.perf_counter()
    for i in range(args.steps):
        x, y, wait = next_batch()
        waits.append(wait)
        state, metrics = step(state, x, y, jax.random.fold_in(key, i))
        if i == args.steps // 2:
            # mid-run async checkpoint: device sync now, write in background
            t = time.perf_counter()
            save_checkpoint_file(os.path.join(tmp, "ckpt.msgpack"), state,
                                 {"step": i}, async_write=True)
            ckpt_s = time.perf_counter() - t
        if i and i % 25 == 0:
            _log(f"  step {i}: wait={wait * 1000:.0f}ms "
                 f"loss={float(metrics['loss']):.3f}")
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    wait_pending_saves()

    waits_np = np.asarray(waits)
    row = {
        "metric": "dress_rehearsal_e2e",
        "model": args.model, "size": args.size, "chans": chans,
        "batch": args.batch, "steps": args.steps, "workers": args.workers,
        "device": dev.device_kind,
        "value": round(args.batch * args.steps / dt, 2),
        "unit": "clips/sec/chip (end-to-end incl. host pipeline)",
        "frames_per_sec": round(args.batch * 4 * args.steps / dt, 2),
        "step_ms": round(dt / args.steps * 1000, 2),
        "host_wait_ms_mean": round(float(waits_np.mean()) * 1000, 2),
        "host_wait_ms_p50": round(float(np.median(waits_np)) * 1000, 2),
        "host_wait_ms_max": round(float(waits_np.max()) * 1000, 2),
        "host_wait_frac": round(float(waits_np.sum()) / dt, 4),
        "ckpt_save_call_ms": round(ckpt_s * 1000, 2) if ckpt_s else None,
        "compile_s": round(compile_s, 1),
    }
    with open(args.out, "w") as f:
        json.dump(row, f, indent=1)
    print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
