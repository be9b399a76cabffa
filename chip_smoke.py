"""Quickest proof that the system still starts on the chip.

Drives the main path once on ONE TPU chip through the entry points a user
calls, at the flagship's full width (``efficientnet_deepfake_v4``,
12×600×600, bf16; weights random from a seed), and checks what comes out:

1. *trainer* — ``runners/train.py``'s CLI entry with the ``scripts/train.sh``
   flagship flags on the synthetic dataset: one epoch (8 steps), the eval
   pass, a checkpoint.  Every loss finite, the step count right, the
   checkpoint on disk.
2. *server* — ``python -m deepfake_detection_tpu.runners.serve`` on that
   checkpoint (uint8 wire, buckets 1,4): waits for ``/readyz`` phase
   ``ready``, POSTs seeded 4-frame clips singly and in a concurrent burst.
   Every answer 200 with finite scores, a clip of identical frames scores
   like its single frame, the request books balance, a batch > 1 ran and
   nothing compiled after ready.
3. *kernels* — a B4-width MBConv stage with the fused depthwise kernel and
   a depth-1 ViT-B/16 with flash attention, compiled by Mosaic (never
   interpreted), against the stock XLA path at float32 ``highest``.

``--four-chips`` runs ONLY the four-device trainer and the one-device run
it is compared with (same seed, same global batch, same process; float32,
see ``FOUR_CHIP_FLAGS``).

One process per chip: this file never imports jax in the parent; each phase
that needs the chip is a child that exits before the next starts.  No chip
is an error (exit 2, nothing compiled, no result line); a failed phase
exits 1 with ``"ok": false``.  The last stdout line is the result::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import base64
import dataclasses
import io
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# scripts/train.sh's flagship flags; the per-chip batch and remat policy
# are what the v5e AOT compile showed to fit 16 GB (CHANGES.md PR 22)
FLAGSHIP_FLAGS = [
    "--data", "", "--dataset", "synthetic",
    "--model", "efficientnet_deepfake_v4", "--model-version", "v4",
    "--input-size-v2", "12,600,600",
    "--opt", "rmsproptf", "--basic-lr", "5e-7",
    "--sched", "step", "--decay-epochs", "2", "--decay-rate", ".92",
    "--epochs", "1", "--amp",
    "--reprob", "0.2", "--remax", "0.05",
    "--flicker", "0.05", "--rotate-range", "5", "--blur-prob", "0.05",
    "--bn-momentum", "0.001", "--mixup", "0.1", "--label-balance",
    "--eval-metric", "loss", "--workers", "8",
    "--experiment", "flagship", "--auto-resume",
    "--recovery-interval", "500", "--log-interval", "1",
]
ONE_CHIP_BATCH = 3                  # the reference's -b 3
CHECKPOINT_POLICY = "none"
FOUR_CHIP_GLOBAL_BATCH = 4          # 1 per chip; 4 on the one-device run
# The four-device comparison trains in float32 with full remat (what lets
# the one-device side hold the global batch in 16 GB): at a fresh init the
# flagship's train-mode loss is chaotic in the reduction order — in bf16
# four devices and one differ by ~20% on the FIRST loss, in float32 by
# ~1e-4 (4 virtual CPU devices, CHANGES.md PR 22).
FOUR_CHIP_FLAGS = ["--compute-dtype", "float32",
                   "--checkpoint-policy", "full",
                   # one BN statistic over the GLOBAL batch in both runs:
                   # the default local BN normalizes per device, which one
                   # device cannot mimic
                   "--sync-bn"]
FOUR_CHIP_LOSS_RTOL = 1e-2
SERVE_BUCKETS = "1,4"
IMAGE_SIZE, IMG_NUM = 600, 4


_T0 = time.monotonic()


def _say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------------------
# children: the only code that touches jax
# ---------------------------------------------------------------------------

def _require_tpu(count: int):
    """jax + its devices, which must be ``count`` TPU chips — checked
    before anything is compiled."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu" or len(devices) != count:
        print(f"chip_smoke: need {count} TPU chip(s); jax found "
              f"{len(devices)} {d.platform!r} device(s)", file=sys.stderr)
        raise SystemExit(2)
    return jax, devices


def _device_facts(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _memory(devices) -> list:
    return [{k: int(v) for k, v in (d.memory_stats() or {}).items()
             if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
            for d in devices]


def _cache_counter(jax):
    """Persistent-compile-cache hits/misses of this process, from jax's
    own monitoring events."""
    from jax import monitoring
    seen = {"hits": 0, "misses": 0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            seen["misses"] += 1
    monitoring.register_event_listener(on_event)
    return seen


def _emit(result: dict) -> None:
    print("RESULT " + json.dumps(result), flush=True)


def child_train(out_dir: str) -> None:
    jax, devices = _require_tpu(1)
    cache = _cache_counter(jax)
    from deepfake_detection_tpu.runners.train import launch_main
    metrics = launch_main(FLAGSHIP_FLAGS + [
        "-b", str(ONE_CHIP_BATCH), "--checkpoint-policy", CHECKPOINT_POLICY,
        "--output", out_dir])
    _emit({"device": _device_facts(devices), "memory": _memory(devices),
           "compile_cache": cache,
           "cache_dir": jax.config.jax_compilation_cache_dir,
           "eval": {k: float(v) for k, v in metrics.items()
                    if isinstance(v, (int, float))}})


def _rel_l2(a_tree, b_tree) -> float:
    import jax
    import numpy as np
    a = np.concatenate([np.asarray(x, np.float64).ravel()
                        for x in jax.tree.leaves(a_tree)])
    b = np.concatenate([np.asarray(x, np.float64).ravel()
                        for x in jax.tree.leaves(b_tree)])
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-30))


def _compile_and_run(jax, fn, *args):
    """``(fn(*args), whether the compiled program holds a Mosaic kernel)``
    from ONE compile."""
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled(*args), "tpu_custom_call" in compiled.as_text()


def kernels_mbconv(jax, batch: int = 8, hw: int = 95) -> dict:
    """EfficientNet-B4 stage 3 (32→56, k5: a stride-2 then a stride-1
    block, 192/336 depthwise channels, SE) fused vs stock."""
    import jax.numpy as jnp
    import numpy as np
    from deepfake_detection_tpu.models.efficientnet_blocks import \
        InvertedResidual

    def blocks(fd):
        return [InvertedResidual(out_chs=56, dw_kernel_size=5, stride=s,
                                 exp_ratio=6.0, se_ratio=0.25, act="silu",
                                 fused_depthwise=fd) for s in (2, 1)]
    stock, fused = blocks("off"), blocks("pallas")
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, hw, hw, 32)), jnp.float32)
    vs, h = [], x
    for i, blk in enumerate(stock):
        v = jax.jit(lambda k, h, blk=blk: blk.init(k, h, training=False))(
            jax.random.PRNGKey(i), h)
        vs.append(v)
        h = blk.apply(v, h, training=False)

    def stage(blks, params, x, training):
        for blk, p, v in zip(blks, params, vs):
            var = {"params": p, "batch_stats": v["batch_stats"]}
            x = blk.apply(var, x, training=True, mutable=["batch_stats"])[0] \
                if training else blk.apply(var, x, training=False)
        return x

    params = [v["params"] for v in vs]
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, blks in (("stock", stock), ("fused", fused)):
            y, k_fwd = _compile_and_run(
                jax, lambda p, x, b=blks: stage(b, p, x, False), params, x)
            g, k_bwd = _compile_and_run(jax, jax.grad(
                lambda p, x, b=blks: jnp.sum(stage(b, p, x, True) ** 2),
                argnums=(0, 1)), params, x)
            out[name] = (y, g)
    compiled = k_fwd and k_bwd              # of the fused pass, the last
    (y0, g0), (yf, gf) = out["stock"], out["fused"]
    err = float(jnp.max(jnp.abs(yf - y0) - 1e-5 * jnp.abs(y0)))
    g_rel = _rel_l2(g0, gf)
    finite = bool(jnp.isfinite(yf).all()) and math.isfinite(g_rel)
    # tests/test_depthwise_pallas.py: outputs rtol=atol=1e-5
    # (test_block_train_parity), model-level grads rel-L2 < 5e-4
    return {"kernel": "fused_depthwise", "mosaic_compiled": compiled,
            "shape": [batch, hw, hw, 32], "out_excess_over_rtol": err,
            "grad_rel_l2": g_rel,
            "ok": compiled and finite and err <= 1e-5 and g_rel < 5e-4}


def kernels_vit(jax, batch: int = 8, size: int = 224) -> dict:
    """ViT-B/16 cut to depth 1 (197 tokens, 12 heads of 64), flash vs
    dense attention on the same weights."""
    import jax.numpy as jnp
    import numpy as np
    from deepfake_detection_tpu.models import create_model, init_model
    dense = create_model("vit_base_patch16_224", num_classes=2).clone(depth=1)
    flash = dense.clone(attn_impl="flash")
    v = init_model(dense, jax.random.PRNGKey(0), (1, size, size, 3))
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (batch, size, size, 3)), jnp.float32)
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, m in (("dense", dense), ("flash", flash)):
            y, k_fwd = _compile_and_run(
                jax, lambda v, x, m=m: m.apply(v, x, training=False), v, x)
            g, k_bwd = _compile_and_run(jax, jax.grad(
                lambda p, x, m=m: jnp.sum(m.apply(
                    {**v, "params": p}, x, training=False) ** 2)),
                v["params"], x)
            out[name] = (y, g)
    compiled = k_fwd and k_bwd              # of the flash pass, the last
    (y0, g0), (yf, gf) = out["dense"], out["flash"]
    err = float(jnp.max(jnp.abs(yf - y0) - 1e-4 * jnp.abs(y0)))
    g_rel = _rel_l2(g0, gf)
    finite = bool(jnp.isfinite(yf).all()) and math.isfinite(g_rel)
    # tests/test_flash_attention.py::test_jit_and_vit_integration:
    # logits rtol=atol=1e-4
    return {"kernel": "flash_attention", "mosaic_compiled": compiled,
            "tokens": (size // 16) ** 2 + 1, "logit_excess_over_rtol": err,
            "grad_rel_l2": g_rel,
            "ok": compiled and finite and err <= 1e-4 and g_rel < 5e-4}


def child_kernels() -> None:
    jax, devices = _require_tpu(1)
    from deepfake_detection_tpu.utils.compile_cache import \
        setup_compile_cache
    setup_compile_cache()
    assert jax.default_backend() == "tpu"   # interpret=None → compiled
    _emit({"device": _device_facts(devices),
           "kernels": [kernels_mbconv(jax), kernels_vit(jax)]})


def _run_trainer_observed(jax, flags, devices) -> dict:
    """``runners/train.py``'s ``launch_main`` on ``devices`` with its train
    step observed: where the batch and the updated parameters really live.

    Everything the run computes is the trainer's own; only three module
    names are rebound for the call — the default mesh's device list, the
    synthetic set's size (it follows the PER-DEVICE batch, and both runs
    must draw the same set) and the step factory (wrapped to look at its
    arguments)."""
    import numpy as np
    from deepfake_detection_tpu.runners import train as T
    seen: dict = {}
    mesh0, data0, step0 = T.make_train_mesh, T.build_datasets, \
        T.make_train_step

    def make_step(*a, **kw):
        step = step0(*a, **kw)

        def observed(state, x, y, rng):
            if "batch" not in seen:
                seen["batch"] = {
                    # same number in both runs = same first global batch
                    "abs_sum": float(np.abs(np.asarray(x, np.float32)).sum()),
                    "spec": str(x.sharding.spec),
                    "shards": [[str(s.device), list(s.data.shape)]
                               for s in x.addressable_shards]}
                leaf = jax.tree.leaves(state.params)[0]
                seen["param_before"] = np.asarray(
                    leaf.addressable_shards[0].data)
            state, metrics = step(state, x, y, rng)
            seen["state"], seen["memory"] = state, _memory(devices)
            return state, metrics
        return observed

    T.make_train_mesh = lambda **kw: mesh0(devices=devices, **kw)
    T.build_datasets = lambda cfg, *a, **kw: data0(dataclasses.replace(
        cfg, batch_size=FOUR_CHIP_GLOBAL_BATCH), *a, **kw)
    T.make_train_step = make_step
    try:
        T.launch_main(flags)
    finally:
        T.make_train_mesh, T.build_datasets, T.make_train_step = \
            mesh0, data0, step0
    leaf = jax.tree.leaves(seen.pop("state").params)[0]
    copies = [np.asarray(s.data) for s in leaf.addressable_shards]
    seen["param"] = {
        "spec": str(leaf.sharding.spec),
        "devices": [str(s.device) for s in leaf.addressable_shards],
        "replicas_identical": all(
            np.array_equal(copies[0], c) for c in copies[1:]),
        "updated": not np.array_equal(copies[0], seen.pop("param_before"))}
    return seen


def child_four_chips(out_dir: str) -> None:
    jax, devices = _require_tpu(4)
    flags = [f for f in FLAGSHIP_FLAGS if f != "--amp"] + FOUR_CHIP_FLAGS
    four = _run_trainer_observed(jax, flags + [
        "-b", str(FOUR_CHIP_GLOBAL_BATCH // 4),
        "--output", os.path.join(out_dir, "four")], devices)
    one = _run_trainer_observed(jax, flags + [
        "-b", str(FOUR_CHIP_GLOBAL_BATCH),
        "--output", os.path.join(out_dir, "one")], devices[:1])
    _emit({"device": _device_facts(devices), "four": four, "one": one})


# ---------------------------------------------------------------------------
# parent: jax-free
# ---------------------------------------------------------------------------

class PhaseFailed(Exception):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def _run_child(phase: str, work: str, timeout: float) -> dict:
    """One chip-holding child to completion; its RESULT line, parsed."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--work", work]
    _say(f"phase {phase}: {' '.join(cmd[1:])}")
    log = os.path.join(work, f"{phase}.log")
    with open(log, "w") as f:
        proc = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE,
                                stderr=f, text=True)
        result, timer = None, threading.Timer(timeout, proc.kill)
        timer.start()
        try:
            for line in proc.stdout:
                if line.startswith("RESULT "):
                    result = json.loads(line[len("RESULT "):])
                else:
                    sys.stdout.write(line)
            rc = proc.wait()
        finally:
            timer.cancel()
            proc.kill()
    if rc == 2:
        with open(log) as f:
            sys.stderr.write(f.read()[-2000:])
        raise SystemExit(2)                 # no chip: no result line
    if rc != 0 or result is None:
        with open(log) as f:
            sys.stderr.write(f.read()[-6000:])
        raise PhaseFailed(f"{phase} child exited {rc}")
    return result


def _telemetry(run_dir: str) -> list:
    with open(os.path.join(run_dir, "telemetry.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def _step_losses(records: list) -> list:
    return [r["loss"] for r in records if r["type"] == "metrics"]


def phase_train(work: str) -> tuple:
    out = os.path.join(work, "train")
    res = _run_child("train", work, timeout=900)
    run = os.path.join(out, "flagship")
    records = _telemetry(run)
    steps = [r for r in records if r["type"] == "metrics"]
    expected = max(ONE_CHIP_BATCH * 8, 16) // ONE_CHIP_BATCH
    losses = _step_losses(steps)
    _say(f"train: device {res['device']}, batch {ONE_CHIP_BATCH}, "
         f"remat {CHECKPOINT_POLICY}, {len(steps)} steps, losses "
         f"{[round(v, 5) for v in losses]}")
    _say(f"train: step wall ms {[r['step_ms'] for r in steps]} (first "
         f"includes the compile), eval {res['eval']}")
    _say(f"train: memory {res['memory']}, compile cache "
         f"{res['compile_cache']} at {res['cache_dir']}")
    _check(res["device"]["platform"] == "tpu", "trainer did not run on TPU")
    _check(len(steps) == expected and
           steps[-1]["counters"]["steps_total"] == expected,
           f"expected {expected} train steps, saw {len(steps)}")
    _check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    _check(math.isfinite(res["eval"].get("loss", float("nan"))),
           f"non-finite eval loss {res['eval']}")
    _check(any(r.get("event") == "epoch_end" for r in records),
           "no epoch_end event: the eval pass did not finish")
    ckpt = os.path.join(run, "checkpoint-0.ckpt")
    _check(os.path.isfile(ckpt) and os.path.getsize(ckpt) > 1 << 20,
           f"no checkpoint at {ckpt}")
    return res["device"], ckpt


def _http(method: str, url: str, body: bytes = None, timeout: float = 60):
    req = urllib.request.Request(url, data=body, method=method, headers={
        "Content-Type": "application/json"} if body else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _metrics(base: str) -> dict:
    status, body = _http("GET", base + "/metrics")
    _check(status == 200, f"/metrics answered {status}")
    out = {}
    for line in body.decode().splitlines():
        if line.startswith("dfd_serving_") and "{" not in line:
            name, _, value = line.partition(" ")
            out[name[len("dfd_serving_"):]] = float(value)
    return out


def _jpeg_frames(seed: int, n: int) -> list:
    """``n`` seeded canvas-sized frames, JPEG-encoded then base64."""
    import numpy as np
    from PIL import Image
    rng = np.random.default_rng(seed)
    frames = []
    for _ in range(n):
        # smooth low-frequency content: a face crop, not white noise
        small = rng.integers(0, 256, (20, 20, 3), dtype=np.uint8)
        img = Image.fromarray(small).resize((IMAGE_SIZE, IMAGE_SIZE),
                                            Image.BICUBIC)
        buf = io.BytesIO()
        img.save(buf, format="JPEG", quality=90)
        frames.append(base64.b64encode(buf.getvalue()).decode())
    return frames


def _score(base: str, payload: dict) -> dict:
    status, body = _http("POST", base + "/score",
                         json.dumps(payload).encode())
    _check(status == 200, f"/score answered {status}: {body[:300]!r}")
    ans = json.loads(body)
    scores = ans["scores"]
    _check(len(scores) == 2 and all(math.isfinite(s) for s in scores) and
           abs(sum(scores) - 1.0) < 1e-3 and
           math.isfinite(ans["fake_score"]), f"bad scores {ans}")
    return ans


def phase_serve(work: str, ckpt: str) -> None:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    cmd = [sys.executable, "-m", "deepfake_detection_tpu.runners.serve",
           "--model", "efficientnet_deepfake_v4", "--model-path", ckpt,
           "--image-size", str(IMAGE_SIZE), "--img-num", str(IMG_NUM),
           "--wire", "uint8", "--buckets", SERVE_BUCKETS,
           "--port", str(port)]
    _say("phase serve: " + " ".join(cmd[1:]))
    t0 = time.monotonic()
    with open(os.path.join(work, "serve.log"), "w") as log:
        proc = subprocess.Popen(cmd, cwd=HERE, stdout=log, stderr=log)
    try:
        detail = None
        while time.monotonic() - t0 < 600:
            _check(proc.poll() is None,
                   f"server exited {proc.returncode} before ready")
            try:
                status, body = _http("GET", base + "/readyz", timeout=5)
            except OSError:
                status = 0
            if status == 200:
                detail = json.loads(body)
                if detail["phase"] == "ready":
                    break
            time.sleep(1.0)
        _check(detail is not None and detail.get("phase") == "ready",
               "server not ready within 600 s")
        _say(f"serve: ready after {time.monotonic() - t0:.1f}s on "
             f"{detail['device']}, models {detail['models']}")
        _check(detail["device"]["platform"] == "tpu",
               f"engine reports {detail['device']}, not a TPU")
        at_ready = _metrics(base)

        clips = [_jpeg_frames(seed, IMG_NUM) for seed in range(7)]
        latencies, answers = [], []
        for clip in clips[:2]:                      # one at a time
            t = time.monotonic()
            answers.append(_score(base, {"frames_b64": clip}))
            latencies.append(round((time.monotonic() - t) * 1e3, 1))
        # identical frames must score like the replicated single frame
        # (serving/http.py; two different compiled programs)
        single = _score(base, {"image_b64": clips[0][0]})
        same = _score(base, {"frames_b64": [clips[0][0]] * IMG_NUM})
        drift = abs(single["fake_score"] - same["fake_score"])
        burst: list = [None] * 4                    # a concurrent burst

        def fire(i):
            try:
                burst[i] = _score(base, {"frames_b64": clips[3 + i]})
            except Exception as e:   # noqa: BLE001 — re-raised below
                burst[i] = e
        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(4)]
        t = time.monotonic()
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
        burst_ms = round((time.monotonic() - t) * 1e3, 1)
        for b in burst:
            if not isinstance(b, dict):
                raise PhaseFailed(f"burst request failed: {b!r}")
        answers += [single, same] + burst
        after = _metrics(base)
        _say(f"serve: {len(answers)} requests 200, fake scores "
             f"{[round(a['fake_score'], 6) for a in answers]}")
        _say(f"serve: single-request latency ms {latencies}, burst of 4 in "
             f"{burst_ms} ms, identical-frames drift {drift:.3g}")
        d = {k: after[k] - at_ready.get(k, 0.0) for k in after}
        _say(f"serve: books since ready accepted {d['accepted_total']} = "
             f"scored {d['scored_total']} + shed {d['shed_total']} + "
             f"deadline {d['deadline_total']} + failed {d['failed_total']} "
             f"+ cache_hit {d['cache_hit_total']}; batches "
             f"{d['batches_total']} rows {d['batch_rows_total']}; backend "
             f"compiles {at_ready['backend_compiles_total']} -> "
             f"{after['backend_compiles_total']}")
        _check(drift <= 1e-5, f"identical-frames clip drifted {drift}")
        _check(d["accepted_total"] == len(answers) == d["scored_total"] and
               d["shed_total"] == d["deadline_total"] == d["failed_total"]
               == 0, f"request books do not balance: {d}")
        _check(d["batch_rows_total"] > d["batches_total"],
               "no device batch larger than 1 ran")
        _check(d["backend_compiles_total"] == 0 and d["compiles_total"] == 0,
               "something compiled after ready")
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(30)


def phase_kernels(work: str) -> None:
    res = _run_child("kernels", work, timeout=600)
    for k in res["kernels"]:
        _say(f"kernels: {k}")
    _check(res["device"]["platform"] == "tpu", "kernels did not run on TPU")
    _check(all(k["ok"] for k in res["kernels"]),
           "a compiled kernel disagrees with the XLA path")


def phase_four_chips(work: str) -> dict:
    res = _run_child("four_chips", work, timeout=1700)
    four, one = res["four"], res["one"]
    l4, l1 = (_step_losses(_telemetry(os.path.join(
        work, "four_chips", run, "flagship"))) for run in ("four", "one"))
    _say(f"four-chips: device {res['device']}, global batch "
         f"{FOUR_CHIP_GLOBAL_BATCH}, {' '.join(FOUR_CHIP_FLAGS)}")
    _say(f"four-chips: batch {four['batch']}")
    _say(f"four-chips: parameter {four['param']}")
    _say(f"four-chips: memory while the 4-device run trained "
         f"{four['memory']}")
    _say(f"four-chips: losses on 4 devices {l4}")
    _say(f"four-chips: losses on 1 device  {l1}")
    _check(res["device"] == {**res["device"], "platform": "tpu", "count": 4},
           f"not four TPU chips: {res['device']}")
    shards = four["batch"]["shards"]
    _check(len({dev for dev, _ in shards}) == 4 and all(
        shape[0] == FOUR_CHIP_GLOBAL_BATCH // 4 for _, shape in shards),
        f"batch not split over four devices: {shards}")
    p = four["param"]
    _check(len(set(p["devices"])) == 4 and p["replicas_identical"] and
           p["updated"], f"parameter update not on four devices: {p}")
    _check(len(one["batch"]["shards"]) == 1, "comparison run not on 1 device")
    _check(all(m.get("bytes_in_use", 0) > 0 for m in four["memory"]) and
           len(four["memory"]) == 4, "a device reports no memory in use")
    _check(len(l4) == len(l1) > 0 and all(map(math.isfinite, l4 + l1)),
           "missing or non-finite losses")
    worst = max(abs(a - b) / abs(b) for a, b in zip(l4, l1))
    _say(f"four-chips: largest relative loss difference {worst:.3g} "
         f"(limit {FOUR_CHIP_LOSS_RTOL})")
    # the device prologue normalizes the batch under two different
    # programs, so the two sums agree to float rounding, not bit for bit
    s4, s1 = four["batch"]["abs_sum"], one["batch"]["abs_sum"]
    _say(f"four-chips: first batch |x| sum {s4} on 4 devices, {s1} on 1")
    _check(abs(s4 - s1) <= 1e-4 * abs(s1),
           "the two runs did not draw the same first batch")
    _check(worst <= FOUR_CHIP_LOSS_RTOL,
           f"per-step losses disagree beyond rtol {FOUR_CHIP_LOSS_RTOL}")
    return res["device"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device trainer and the "
                         "one-device run it is compared with")
    ap.add_argument("--phase", help=argparse.SUPPRESS)   # a child of ours
    ap.add_argument("--work", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "deepfake_detection_tpu")):
        print("chip_smoke: no deepfake_detection_tpu package beside this "
              "file — it drives the program, it is not the program",
              file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    if args.phase:
        out = os.path.join(args.work, args.phase)
        {"train": lambda: child_train(out), "kernels": child_kernels,
         "four_chips": lambda: child_four_chips(out)}[args.phase]()
        return 0

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    device = None
    try:
        if args.four_chips:
            device = phase_four_chips(work)
        else:
            device, ckpt = phase_train(work)
            phase_serve(work, ckpt)
            phase_kernels(work)
    except PhaseFailed as e:
        _say(f"FAILED: {e}")
        print(json.dumps({"ok": False, "error": str(e), "device": device}),
              flush=True)
        return 1
    finally:
        keep = os.path.join(HERE, "chiprun_out", "chip_smoke")
        os.makedirs(keep, exist_ok=True)
        for root, _, files in os.walk(work):    # logs + telemetry only
            for name in files:
                if name.endswith((".log", ".jsonl")):
                    rel = os.path.relpath(os.path.join(root, name), work)
                    shutil.copy(os.path.join(root, name), os.path.join(
                        keep, rel.replace(os.sep, "__")))
        shutil.rmtree(work, ignore_errors=True)
    _say(f"all phases passed in {time.monotonic() - _T0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
