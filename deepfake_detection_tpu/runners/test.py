"""Single-image inference runner.

Parity with ``/root/reference/dfd/runners/test.py``: load the flagship
checkpoint, preprocess each image (aspect-preserving resize + center pad to
600×600, normalize, replicate ×4 → 12 channels, :49-58), print the softmax
fake score (``scores[:, 0]``, :58-60).

Usage::

    python -m deepfake_detection_tpu.runners.test img1.png img2.jpg \
        [--model-path PATH] [--image-size 600]
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from PIL import Image

from ..models import create_deepfake_model_v4, init_model
from ..models.helpers import load_checkpoint
from ..params import (image_max_height, img_num, make_score_fn,
                      normalize_concat, normalize_replicate, prepare_canvas)

__all__ = ["test_img", "preprocess", "preprocess_clip"]


def preprocess(img_file, size: int = image_max_height,
               num: int = img_num) -> np.ndarray:
    """file (path or file-like) → (1, H, W, 3*num) normalized float32
    (reference test.py:49-56).  The two halves live in ``params.py`` so the
    serving subsystem (serving/engine.py) reuses them verbatim: geometric
    canvas on host, photometrics replicated on device."""
    img = np.asarray(Image.open(img_file).convert("RGB"), np.uint8)
    return normalize_replicate(prepare_canvas(img, size), num)[None]


def preprocess_clip(img_files, size: int = image_max_height,
                    num: int = img_num) -> np.ndarray:
    """``num`` frame files → ONE (1, H, W, 3*num) temporal clip: each frame
    gets the geometric canvas, then the frames channel-concatenate
    (``params.normalize_concat``) instead of replicating one frame — the
    multi-frame wire the streaming windower and ``--clip`` mode score.
    ``num`` identical files reproduce :func:`preprocess` bit-for-bit."""
    canvases = [prepare_canvas(
        np.asarray(Image.open(f).convert("RGB"), np.uint8), size)
        for f in img_files]
    return normalize_concat(canvases, num)[None]


def test_img(model_path: Optional[str], img_files: Sequence[str],
             size: int = image_max_height, clip: bool = False,
             dtype: str = "f32") -> List[float]:
    """Score images one at a time (replicate ×img_num, reference parity),
    or — with ``clip=True`` — in groups of ``img_num`` distinct frames
    channel-concatenated into temporal clips (the streaming windower's
    layout; scores are bit-identical to the serving float32 wire).

    ``dtype`` applies the serving PTQ transform (``serving/quant.py``)
    to the loaded f32 weights before scoring — the same quantized tree
    and the same variables-as-argument program the engine serves, so
    this CLI is the parity harness's non-server oracle: bit-identical
    to the engine's float32 wire at f32, and within the measured
    SERVE_BENCH.md tolerance under bf16/int8."""
    assert all(os.path.isfile(f) for f in img_files), "file not exist!"
    if clip and len(img_files) % img_num:
        raise ValueError(f"--clip needs a multiple of img_num={img_num} "
                         f"images, got {len(img_files)}")
    print(f"To load model from {model_path}")
    model = create_deepfake_model_v4("efficientnet_deepfake_v4",
                                     num_classes=2, in_chans=12)
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, size, size, 12))
    if model_path and os.path.isdir(model_path):
        # sharded (--ckpt-sharded) training checkpoint directory; prefers
        # the EMA stream like the reference's released model_half
        from ..train.checkpoint import load_sharded_for_eval
        variables = load_sharded_for_eval(model_path, variables)
    elif model_path:
        variables = load_checkpoint(variables, model_path, strict=False)
    print("Model loaded!")
    if dtype not in ("f32", "float32"):
        from ..serving.quant import quant_summary, quantize_tree
        variables = quantize_tree(variables, dtype)
        print(f"Quantized weights to {dtype}: {quant_summary(variables)}")
    score_fn = make_score_fn(model, variables)
    scores_out: List[float] = []
    if clip:
        for i in range(0, len(img_files), img_num):
            group = list(img_files[i:i + img_num])
            scores = np.asarray(score_fn(jnp.asarray(
                preprocess_clip(group, size))))
            fake_score = float(scores[0, 0])                # P(fake)
            scores_out.append(fake_score)
            print(f"clip {group}'s fake score:{fake_score}")
        return scores_out
    for img_file in img_files:
        scores = np.asarray(score_fn(jnp.asarray(preprocess(img_file, size))))
        fake_score = float(scores[0, 0])                    # P(fake)
        scores_out.append(fake_score)
        print(f"{img_file}'s fake score:{fake_score}")
    return scores_out


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="deepfake single-image inference")
    p.add_argument("images", nargs="*")
    p.add_argument("--model-path", default="")
    p.add_argument("--image-size", type=int, default=image_max_height)
    p.add_argument("--clip", action="store_true",
                   help=f"score groups of img_num={img_num} distinct "
                        f"frames as temporal clips instead of replicating "
                        f"each image")
    p.add_argument("--dtype", default="f32",
                   choices=["f32", "bf16", "int8"],
                   help="post-training quantization of the loaded f32 "
                        "weights (serving/quant.py): f32 = reference "
                        "parity, bf16/int8 = the engine's PTQ serving "
                        "modes (tools/quant_parity.py measures the drift)")
    args = p.parse_args(argv)
    from ..utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    if not args.images:
        print("Please input your images. e.g. python -m "
              "deepfake_detection_tpu.runners.test image1 image2")
        return
    test_img(args.model_path or None, args.images, size=args.image_size,
             clip=args.clip, dtype=args.dtype)


if __name__ == "__main__":
    main(sys.argv[1:])
