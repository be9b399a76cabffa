#!/usr/bin/env python
"""AUC/score-drift parity harness for the PTQ serving path (ISSUE 14).

Scores a **seeded eval list** under f32, bf16 and int8 through the exact
variables-as-argument program the serving engine compiles (one padded
batch per dtype — `params.make_score_fn` semantics with
`serving/quant.py`'s transform), then **hard-fails** if either quantized
mode drifts past the pre-registered bounds:

* **score drift** — max |P_fake_quant − P_fake_f32| over the eval set;
* **agreement AUC** — AUC of the quantized scores against the f32
  verdicts (labels = f32 score above its own median, so both classes are
  always populated); 1.0 = the quantized model ranks every clip exactly
  as the f32 oracle does at the operating point;
* **decision agreement** — fraction of clips whose 0.5-threshold verdict
  is unchanged.

Bounds are *pre-registered* in SERVE_BENCH.md — this tool is the gate
that keeps them honest: a quantization change that silently degrades
scores fails CI here, never in production.  Misses are stated plainly
(each violated bound named with its measured value), exit code 1.

Eval inputs: either ``--images`` (files on disk, the real-data mode) or
the default deterministic synthetic set (seeded gradients + noise, the
bench_serve idiom).  With no ``--model-path`` the seed-0 init is
perturbed (``--perturb-scale``) so scores are discriminative — the same
idiom the serving tests use; pass a real checkpoint for release gating.

    JAX_PLATFORMS=cpu \
        python tools/quant_parity.py --image-size 32 --img-num 1 --n 64
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _log(msg: str) -> None:
    print(f"[quant_parity] {msg}", file=sys.stderr, flush=True)


def make_canvases(n: int, size: int, src_size: int,
                  seed: int = 0) -> List[np.ndarray]:
    """Deterministic synthetic eval canvases.

    Four texture families (smooth gradients, wide-band noise, flat
    blocks, checkerboards) at per-image brightness/contrast/noise draws:
    the spread matters — an eval set whose f32 scores collapse to one
    value cannot rank anything, and the AUC gate would then measure tie-
    breaking noise instead of quantization error (the harness warns when
    that happens)."""
    from deepfake_detection_tpu.params import prepare_canvas
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:src_size, 0:src_size].astype(np.float32)
    out = []
    for i in range(n):
        kind = i % 4
        brightness = float(rng.uniform(40, 215))
        contrast = float(rng.uniform(20, 100))
        noise = float(rng.uniform(0, 40))
        if kind == 0:                      # smooth gradients
            base = brightness + contrast * np.sin(
                xx / (4 + i % 9) + i) * np.cos(yy / (5 + i % 7))
        elif kind == 1:                    # wide-band noise
            base = brightness + np.zeros_like(xx)
            noise = max(noise, 30.0)
        elif kind == 2:                    # flat block w/ hard edge
            base = np.where(xx > src_size * rng.uniform(0.2, 0.8),
                            brightness + contrast, brightness - contrast)
        else:                              # checkerboard
            period = int(rng.integers(2, 16))
            base = brightness + contrast * (
                ((xx // period + yy // period) % 2) * 2 - 1)
        img = np.stack([base + rng.normal(0, noise, base.shape)
                        for _ in range(3)], axis=-1)
        out.append(prepare_canvas(
            np.clip(img, 0, 255).astype(np.uint8), size))
    return out


def load_canvases(paths: List[str], size: int) -> List[np.ndarray]:
    from PIL import Image

    from deepfake_detection_tpu.params import prepare_canvas
    out = []
    for p in paths:
        img = np.asarray(Image.open(p).convert("RGB"), np.uint8)
        out.append(prepare_canvas(img, size))
    return out


def rank_auc(scores: np.ndarray, labels: np.ndarray) -> float:
    """Mann-Whitney AUC (tie-aware midranks); nan if one class empty."""
    pos = scores[labels]
    neg = scores[~labels]
    if len(pos) == 0 or len(neg) == 0:
        return float("nan")
    allv = np.concatenate([pos, neg])
    order = np.argsort(allv, kind="mergesort")
    ranks = np.empty(len(allv))
    ranks[order] = np.arange(1, len(allv) + 1)
    # midranks for ties
    sv = allv[order]
    i = 0
    while i < len(sv):
        j = i
        while j + 1 < len(sv) and sv[j + 1] == sv[i]:
            j += 1
        if j > i:
            ranks[order[i:j + 1]] = (i + j) / 2.0 + 1
        i = j + 1
    r_pos = ranks[:len(pos)].sum()
    u = r_pos - len(pos) * (len(pos) + 1) / 2.0
    return float(u / (len(pos) * len(neg)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", default="vit_tiny_patch16_224",
                    help="registered model name (the bench_serve default "
                         "— a random-init CNN pools every input to one "
                         "score, a random-init ViT discriminates; pass "
                         "the flagship + --model-path on real "
                         "accelerators)")
    ap.add_argument("--model-path", default="")
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--img-num", type=int, default=1)
    ap.add_argument("--n", type=int, default=64,
                    help="eval-set size (synthetic mode)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--src-size", type=int, default=96)
    ap.add_argument("--images", nargs="*", default=[],
                    help="score these files instead of the synthetic set")
    ap.add_argument("--perturb-scale", type=float, default=0.05,
                    help="param nudge applied when no --model-path (zero "
                         "heads score a flat 0.5; the serving-test "
                         "idiom makes scores discriminative)")
    # ---- the pre-registered bounds (SERVE_BENCH.md) -------------------
    ap.add_argument("--max-drift-bf16", type=float, default=0.02)
    ap.add_argument("--max-drift-int8", type=float, default=0.06)
    ap.add_argument("--min-auc", type=float, default=0.99,
                    help="agreement-AUC floor for BOTH quantized modes")
    ap.add_argument("--min-agreement", type=float, default=0.97,
                    help="0.5-verdict agreement floor for both modes")
    ap.add_argument("--out", default="", help="write a JSON report here")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from deepfake_detection_tpu.models import create_model, init_model
    from deepfake_detection_tpu.params import normalize_replicate
    from deepfake_detection_tpu.serving.quant import (quant_summary,
                                                      quantize_tree,
                                                      realize_tree)

    size, num = args.image_size, args.img_num
    chans = 3 * num
    model = create_model(args.model, num_classes=2, in_chans=chans)
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, size, size, chans))
    if args.model_path:
        from deepfake_detection_tpu.models.helpers import load_checkpoint
        variables = load_checkpoint(variables, args.model_path,
                                    strict=False)
        _log(f"loaded {args.model_path}")
    elif args.perturb_scale:
        rng = np.random.default_rng(args.seed + 1)
        variables = jax.tree.map(
            lambda a: np.asarray(a) + args.perturb_scale *
            rng.standard_normal(np.shape(a)).astype(np.float32)
            if np.issubdtype(np.asarray(a).dtype, np.floating)
            else np.asarray(a), variables)
        _log(f"no --model-path: seed-0 init perturbed by "
             f"{args.perturb_scale}")

    if args.images:
        canvases = load_canvases(args.images, size)
        _log(f"eval list: {len(canvases)} file(s)")
    else:
        canvases = make_canvases(args.n, size, args.src_size, args.seed)
        _log(f"eval list: {len(canvases)} seeded synthetic canvases "
             f"(seed {args.seed})")
    x = np.stack([normalize_replicate(c, num) for c in canvases])

    # ONE program per dtype — the engine's float32 wire
    # (variables-as-argument, realize_tree in-trace; the f32 trace is
    # structurally identical to make_score_fn's)
    def score(vars_, xx):
        logits = model.apply(realize_tree(vars_), xx, training=False)
        return jax.nn.softmax(logits, axis=-1)

    fn = jax.jit(score)
    x_dev = jnp.asarray(x)
    fakes: Dict[str, np.ndarray] = {}
    for mode in ("f32", "bf16", "int8"):
        qvars = jax.device_put(quantize_tree(variables, mode))
        scores = np.asarray(fn(qvars, x_dev))
        fakes[mode] = scores[:, 0]
        _log(f"{mode}: {quant_summary(qvars)} -> fake scores "
             f"[{fakes[mode].min():.4f}, {fakes[mode].max():.4f}]")

    f32 = fakes["f32"]
    # f32-verdict labels at the MEDIAN operating point: both classes are
    # always populated, so the agreement AUC is defined on any model
    labels = f32 > np.median(f32)
    if labels.all() or not labels.any():
        _log("WARNING: degenerate f32 score distribution (all ties); "
             "AUC undefined, drift bounds still enforced")

    report = {"model": args.model, "image_size": size, "img_num": num,
              "n_eval": len(canvases), "seed": args.seed,
              "model_path": args.model_path, "modes": {}}
    bounds = {"bf16": args.max_drift_bf16, "int8": args.max_drift_int8}
    failures = []
    for mode in ("bf16", "int8"):
        q = fakes[mode]
        drift_max = float(np.abs(q - f32).max())
        drift_mean = float(np.abs(q - f32).mean())
        auc = rank_auc(q, labels)
        agree = float(((q >= 0.5) == (f32 >= 0.5)).mean())
        report["modes"][mode] = {
            "drift_max": drift_max, "drift_mean": drift_mean,
            "agreement_auc": auc, "decision_agreement": agree,
            "bound_drift_max": bounds[mode], "bound_min_auc": args.min_auc,
            "bound_min_agreement": args.min_agreement}
        _log(f"{mode}: drift max {drift_max:.6f} mean {drift_mean:.6f}, "
             f"agreement AUC {auc:.6f}, decision agreement {agree:.4f}")
        if drift_max > bounds[mode]:
            failures.append(f"{mode}: drift_max {drift_max:.6f} > bound "
                            f"{bounds[mode]}")
        if not np.isnan(auc) and auc < args.min_auc:
            failures.append(f"{mode}: agreement AUC {auc:.6f} < bound "
                            f"{args.min_auc}")
        if agree < args.min_agreement:
            failures.append(f"{mode}: decision agreement {agree:.4f} < "
                            f"bound {args.min_agreement}")
    report["failures"] = failures

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        _log(f"wrote {args.out}")
    print(json.dumps(report, indent=2))
    if failures:
        _log("FAIL: " + "; ".join(failures))
        return 1
    _log("PASS: bf16 and int8 inside the pre-registered bounds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
