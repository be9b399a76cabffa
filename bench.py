"""Benchmark: train throughput (frames/sec/chip) on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...,
"rows": [...]} — the headline metric is EfficientNet-B4 (the north-star
benchmark model) and ``rows`` carries the full measured config matrix:
B4 380², the flagship ``efficientnet_deepfake_v4``
12×600² (with an OOM ladder over batch/remat), ViT-B/16 224² with both
dense and Pallas-flash attention, a forward-only B4 inference row
(the reference serves inference from the same backbone, test.py), and
the temporal-extension TimeSformer on 4-frame clips (last in the
matrix, so a budget truncation never costs a reference-parity row).

The reference publishes no numbers (BASELINE.md), so ``vs_baseline`` is
MFU / 0.70 — the fraction of the driver-set north-star target of ≥70% MFU
(BASELINE.json) achieved by the measured step time.  FLOPs come from XLA's
own cost analysis of the compiled train step; peak chip FLOPs from the
device kind.

Env overrides: any of BENCH_MODEL/BENCH_BATCH/BENCH_SIZE/BENCH_CHANS/
BENCH_ATTN/BENCH_REMAT pins a single custom config (skipping the matrix);
BENCH_STEPS sets measured steps in either mode; BENCH_MATRIX=0 runs the
headline config only; BENCH_MATRIX_BUDGET caps the matrix's own wall-time
(default 1200 s, measured from after the headline config — later configs
are skipped, recorded as such, once exceeded).

No chip is an error: the bench measures a TPU or exits non-zero with an
error JSON line — it never continues on the CPU, and a ``device_kind``
missing from the peak table (``obs/telemetry.py``) raises instead of
assuming a peak.
"""

from __future__ import annotations

import json
import os
import sys
import time
from types import SimpleNamespace

_T0 = time.perf_counter()


def _log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _fail_json(stage: str, err: str) -> None:
    print(json.dumps({
        "metric": "train_throughput_error", "value": 0.0,
        "unit": "frames/sec/chip", "vs_baseline": 0.0,
        "error_stage": stage, "error": err[:500],
    }), flush=True)


def _init_backend():
    """``jax.devices()`` — which must be TPU chips."""
    import jax

    from deepfake_detection_tpu.utils.compile_cache import \
        setup_compile_cache
    setup_compile_cache()
    devices = jax.devices()
    _log(f"devices: {devices}")
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU; jax found {devices[0].platform!r} "
            f"devices — nothing is measured on another backend")
    return devices


# Fewest measured steps of a full-quality row; the flagship ladder halves
# BENCH_STEPS but not below this.
_MIN_STEPS = 10


def _run_config(devices, model_name: str, batch: int, size: int, chans: int,
                steps: int, dtype, extra=None, mode: str = "train") -> dict:
    """Measure one config (train step, or forward-only ``mode='infer'``);
    returns a result row."""
    import jax
    import numpy as np

    from deepfake_detection_tpu.losses import cross_entropy
    from deepfake_detection_tpu.models import create_model, init_model
    from deepfake_detection_tpu.optim import create_optimizer
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_eval_step,
                                              make_train_step)

    tag = "/".join(f"{k}={v}" for k, v in (extra or {}).items())
    _log(f"config[{mode}]: {model_name} {size}x{size}x{chans} b{batch} "
         f"steps={steps} {tag} on {devices[0].device_kind}")
    _log("building + initializing model ...")
    import jax.numpy as jnp
    model = create_model(model_name, num_classes=2, in_chans=chans,
                         dtype=dtype if dtype != jnp.float32 else None,
                         **(extra or {}))
    variables = init_model(model, jax.random.PRNGKey(0),
                           (2, size, size, chans), training=True)
    cfg = SimpleNamespace(opt="rmsproptf", opt_eps=1e-8, momentum=0.9,
                          weight_decay=1e-5, lr=1.2e-5)
    # forward-only rows skip optimizer slots and the EMA duplicate (~3-4x
    # param memory a real deployment would not hold)
    import optax
    tx = create_optimizer(cfg) if mode != "infer" else optax.identity()
    state = create_train_state(variables, tx, with_ema=mode != "infer")
    # single chip → no mesh; plain jit path
    if mode == "infer":
        eval_step = make_eval_step(model, cross_entropy)

        def step(state, x, y, key):      # key ignored: deterministic eval
            return state, eval_step(state, x, y)
    else:
        step = make_train_step(model, tx, cross_entropy, mesh=None,
                               bn_mode="global", ema_decay=0.9998)

    # several distinct device-resident batches, cycled during measurement —
    # a single fixed batch gets memorized within ~2 steps (loss→0 in the
    # report) and lets XLA's scheduler see an unrealistically stable stream
    rng = np.random.default_rng(0)
    n_batches = 4
    xs = [jax.device_put(rng.normal(size=(batch, size, size, chans))
                         .astype(np.float32).astype(dtype))
          for _ in range(n_batches)]
    ys = [jax.device_put(rng.integers(0, 2, batch)) for _ in range(n_batches)]
    x, y = xs[0], ys[0]
    key = jax.random.PRNGKey(1)

    # FLOPs of the whole compiled step from XLA cost analysis
    _log(f"lowering + compiling {mode} step ...")
    lowered = jax.jit(step.__wrapped__ if hasattr(step, "__wrapped__")
                      else step).lower(state, x, y, key)
    compiled = lowered.compile()
    try:
        flops_per_step = float(compiled.cost_analysis()["flops"])
    except (KeyError, TypeError):
        flops_per_step = float("nan")
    _log(f"compiled; XLA cost analysis: {flops_per_step:.3e} flops/step")

    # warmup (also primes the donated-buffer path)
    _log("warmup (3 steps) ...")
    for i in range(3):
        state, metrics = step(state, x, y, jax.random.fold_in(key, i))
    jax.block_until_ready(metrics["loss"])

    _log(f"measuring ({steps} steps) ...")
    t0 = time.perf_counter()
    for i in range(steps):
        state, metrics = step(state, xs[i % n_batches], ys[i % n_batches],
                              jax.random.fold_in(key, 100 + i))
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0

    frames_per_sec = batch * steps / dt
    from deepfake_detection_tpu.obs import peak_flops
    peak = peak_flops(devices[0])
    mfu = (flops_per_step * steps / dt) / peak if np.isfinite(
        flops_per_step) else float("nan")
    _log(f"done: {frames_per_sec:.1f} frames/s, "
         f"{dt / steps * 1000:.1f} ms/step, mfu={mfu:.3f}")
    name = f"{model_name}_{size}x{size}x{chans}_b{batch}"
    if extra and extra.get("attn_impl"):
        name += f"_{extra['attn_impl']}"
    row = {
        "metric": f"{'infer' if mode == 'infer' else 'train'}"
                  f"_throughput_{name}",
        "value": round(frames_per_sec, 2),
        "unit": "frames/sec/chip",
        "vs_baseline": round(mfu / 0.70, 4) if np.isfinite(mfu) else None,
        "mfu": round(mfu, 4) if np.isfinite(mfu) else None,
        "step_ms": round(dt / steps * 1000, 2),
        "steps": steps,
        "device": devices[0].device_kind,
        "loss": round(float(metrics["loss"]), 4),
    }
    if extra:
        row["config"] = dict(extra)
    return row


def _is_oom(err: BaseException) -> bool:
    return "resource_exhausted" in repr(err).lower() or \
        "out of memory" in repr(err).lower()


def main() -> None:
    devices = _init_backend()
    import jax.numpy as jnp

    custom = any(os.environ.get(k) for k in
                 ("BENCH_MODEL", "BENCH_BATCH", "BENCH_SIZE", "BENCH_CHANS",
                  "BENCH_ATTN", "BENCH_REMAT"))
    rows = []

    steps = int(os.environ.get("BENCH_STEPS", 20))
    if custom:
        extra = {}
        if os.environ.get("BENCH_ATTN"):
            extra["attn_impl"] = os.environ["BENCH_ATTN"]
        if os.environ.get("BENCH_REMAT"):
            extra["remat_policy"] = os.environ["BENCH_REMAT"]
        rows.append(_run_config(
            devices, os.environ.get("BENCH_MODEL", "efficientnet_b4"),
            int(os.environ.get("BENCH_BATCH", 64)),
            int(os.environ.get("BENCH_SIZE", 380)),
            int(os.environ.get("BENCH_CHANS", 3)),
            steps, jnp.bfloat16, extra or None))
    else:
        # headline first — if the driver kills the matrix midway, the
        # budget check records what was skipped
        budget = float(os.environ.get("BENCH_MATRIX_BUDGET", 1200))
        # batch 64 comes from a sweep that predates the current code
        # (PERF.md §4) — the benchmark PR re-derives it
        matrix = [("b4", lambda: _run_config(
            devices, "efficientnet_b4", 64, 380, 3, steps, jnp.bfloat16))]
        if os.environ.get("BENCH_MATRIX", "1") != "0":
            # flagship: OOM ladder over (batch, remat) — 600²×12 at B7
            # scale; the canonical cluster config is 3/GPU (train.sh:5)
            def flagship():
                for b, remat in ((8, "dots"), (4, "dots"), (2, "full")):
                    try:
                        # debug runs (BENCH_STEPS < 10) stay short
                        fsteps = (max(_MIN_STEPS, steps // 2)
                                  if steps >= _MIN_STEPS
                                  else max(5, steps // 2))
                        return _run_config(
                            devices, "efficientnet_deepfake_v4", b, 600,
                            12, fsteps, jnp.bfloat16,
                            {"remat_policy": remat})
                    except BaseException as e:  # noqa: BLE001
                        if not _is_oom(e):
                            raise
                        _log(f"flagship b{b}/{remat} OOM; stepping down")
                raise RuntimeError("flagship OOM even at b2/full")

            matrix += [
                ("flagship_v4", flagship),
                ("vit_dense", lambda: _run_config(
                    devices, "vit_base_patch16_224", 128, 224, 3, steps,
                    jnp.bfloat16, {"attn_impl": "full"})),
                ("vit_flash", lambda: _run_config(
                    devices, "vit_base_patch16_224", 128, 224, 3, steps,
                    jnp.bfloat16, {"attn_impl": "flash"})),
                # deployment story: forward-only B4 (the reference serves
                # inference from the same backbone, test.py)
                ("b4_infer", lambda: _run_config(
                    devices, "efficientnet_b4", 128, 380, 3, steps,
                    jnp.bfloat16, mode="infer")),
                # the temporal extension flagship: divided space-time
                # attention over the 4-frame clips (models/timesformer.py);
                # last so a budget-truncated matrix never eats the
                # reference-parity rows above
                ("timesformer", lambda: _run_config(
                    devices, "timesformer_base_patch16_224", 32, 224, 12,
                    steps, jnp.bfloat16)),
            ]
        matrix_t0 = None
        for name, fn in matrix:
            if rows and matrix_t0 is None:
                matrix_t0 = time.perf_counter()   # budget excludes init +
                # the headline config
            if matrix_t0 is not None and \
                    time.perf_counter() - matrix_t0 > budget:
                _log(f"matrix budget exceeded; skipping {name}")
                rows.append({"metric": name, "skipped":
                             f"matrix budget {budget:.0f}s exceeded"})
                continue
            try:
                rows.append(fn())
            except BaseException as e:  # noqa: BLE001 — record, continue
                import traceback
                traceback.print_exc()
                _log(f"config {name} failed: {e!r}")
                rows.append({"metric": name, "error": repr(e)[:300]})

    headline = next((r for r in rows if "value" in r), rows[0])
    result = dict(headline)
    result["rows"] = rows
    print(json.dumps(result), flush=True)
    if any("error" in r for r in rows):
        sys.exit(1)


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # noqa: BLE001 — error JSON line, exit 1
        import traceback
        traceback.print_exc()
        _fail_json("run", repr(e))
        sys.exit(1)
