"""Capture a jax.profiler trace of the B4 bench train step on the chip.

VERDICT r3 weak #1: the depthwise-VPU roofline (PERF.md §2) explains the
measured 0.548 MFU analytically but has never been confirmed against a
device trace.  This tool runs the same compiled train step ``bench.py``
measures, under ``jax.profiler.trace``, and leaves the trace directory for
inspection (xplane.pb + trace-viewer json when the backend emits one)::

    python tools/profile_step.py [--model efficientnet_b4] [--batch 64]
        [--size 380] [--steps 10] [--out /tmp/b4_trace]

On CPU this still works (XLA CPU emits traces) but only TPU traces carry
MXU/VPU attribution.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="efficientnet_b4")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--size", type=int, default=380)
    ap.add_argument("--chans", type=int, default=3)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--out", default="/tmp/b4_trace")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from deepfake_detection_tpu.utils.compile_cache import \
        setup_compile_cache
    setup_compile_cache()
    import numpy as np
    from types import SimpleNamespace

    from deepfake_detection_tpu.losses import cross_entropy
    from deepfake_detection_tpu.models import create_model, init_model
    from deepfake_detection_tpu.optim import create_optimizer
    from deepfake_detection_tpu.train import create_train_state, \
        make_train_step

    dev = jax.devices()[0]
    print(f"device: {dev.device_kind}", flush=True)
    model = create_model(args.model, num_classes=2, in_chans=args.chans,
                         dtype=jnp.bfloat16)
    variables = init_model(model, jax.random.PRNGKey(0),
                           (2, args.size, args.size, args.chans),
                           training=True)
    cfg = SimpleNamespace(opt="rmsproptf", opt_eps=1e-8, momentum=0.9,
                          weight_decay=1e-5, lr=1.2e-5)
    tx = create_optimizer(cfg)
    state = create_train_state(variables, tx, with_ema=True)
    step = make_train_step(model, tx, cross_entropy, mesh=None,
                           bn_mode="global", ema_decay=0.9998)
    rng = np.random.default_rng(0)
    x = jax.device_put(rng.normal(
        size=(args.batch, args.size, args.size, args.chans))
        .astype(np.float32).astype(jnp.bfloat16))
    y = jax.device_put(rng.integers(0, 2, args.batch))
    key = jax.random.PRNGKey(1)

    print("warmup (3 steps) ...", flush=True)
    for i in range(3):
        state, metrics = step(state, x, y, jax.random.fold_in(key, i))
    jax.block_until_ready(metrics["loss"])

    print(f"tracing {args.steps} steps -> {args.out}", flush=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(args.out):
        for i in range(args.steps):
            state, metrics = step(state, x, y, jax.random.fold_in(key, 10 + i))
        jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    print(f"traced: {dt / args.steps * 1000:.1f} ms/step "
          f"({args.batch * args.steps / dt:.1f} frames/s)", flush=True)
    for root, _, files in os.walk(args.out):
        for f in files:
            p = os.path.join(root, f)
            print(f"  {os.path.getsize(p):>10} {p}", flush=True)


if __name__ == "__main__":
    main()
