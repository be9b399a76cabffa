"""Closed-loop load generator for the serving subsystem (ISSUE 2).

Spawns ``runners/serve.py`` as a subprocess (or targets ``--url``), drives
``POST /score`` with persistent keep-alive connections at several
concurrency levels, and reports a latency/throughput table plus two
baselines:

* **warm sequential** — the ``runners/test.py`` scoring loop (same model,
  same preprocess, batch-1 jit call per image) in a warmed process: the
  best the one-shot CLI path can do when amortized;
* **cold one-shot** — the same scoring of ONE image in a fresh
  interpreter: what the status-quo CLI actually costs per invocation
  (startup + model build + compile).

Also probes ``/metrics`` around the load phases and **fails loudly if
``compiles_total`` grew after warmup** — the bucketed compile cache's
zero-recompile guarantee is part of the acceptance bar.

Defaults are sized for a small-CPU box (the serving stack is
chip-independent); on real accelerators pass the flagship config.

    JAX_PLATFORMS=cpu \
        python tools/bench_serve.py --out SERVE_BENCH.md
"""

from __future__ import annotations

import argparse
import http.client
import io
import os
import socket
import statistics
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _log(msg: str) -> None:
    print(f"[bench_serve] {msg}", file=sys.stderr, flush=True)


def make_jpegs(n: int, src_size: int, seed: int = 0) -> List[bytes]:
    """Synthetic photographic-ish JPEGs (random noise compresses terribly
    and decodes unrealistically fast; smooth gradients + noise is closer)."""
    from PIL import Image
    out = []
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:src_size, 0:src_size].astype(np.float32)
    for i in range(n):
        base = (128 + 80 * np.sin(xx / (8 + i % 7) + i)
                + 40 * np.cos(yy / (11 + i % 5)))
        img = np.stack([base + rng.normal(0, 12, base.shape)
                        for _ in range(3)], axis=-1)
        img = np.clip(img, 0, 255).astype(np.uint8)
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, "JPEG", quality=88)
        out.append(buf.getvalue())
    return out


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# server lifecycle
# ---------------------------------------------------------------------------

def spawn_server(args, extra: Optional[List[str]] = None,
                 env_extra: Optional[Dict[str, str]] = None
                 ) -> Tuple[subprocess.Popen, str]:
    port = free_port()
    cmd = [sys.executable, "-m", "deepfake_detection_tpu.runners.serve",
           "--model", args.model, "--image-size", str(args.image_size),
           "--img-num", str(args.img_num), "--port", str(port),
           "--buckets", args.buckets,
           "--batch-deadline-ms", str(args.deadline_ms),
           "--max-queue", str(args.max_queue)]
    if args.single_thread_xla:
        cmd += ["--single-thread-xla"]
    if args.wire:
        cmd += ["--wire", args.wire]
    if args.model_path:
        cmd += ["--model-path", args.model_path]
    if getattr(args, "dtype", ""):
        cmd += ["--dtype", args.dtype]
    cmd += list(extra or [])
    env = dict(os.environ)
    if not args.keep_env:
        env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(env_extra or {})
    _log("spawning: " + " ".join(cmd))
    proc = subprocess.Popen(cmd, cwd=_REPO, env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    return proc, f"127.0.0.1:{port}"


def wait_ready(netloc: str, timeout: float = 900.0) -> None:
    host, port = netloc.split(":")
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        try:
            conn = http.client.HTTPConnection(host, int(port), timeout=2)
            conn.request("GET", "/readyz")
            if conn.getresponse().status == 200:
                _log(f"server ready after {time.monotonic() - t0:.1f}s")
                return
        except OSError:
            pass
        time.sleep(0.5)
    raise TimeoutError(f"server at {netloc} not ready within {timeout}s")


def scrape_metrics_labeled(netloc: str) -> Dict[str, float]:
    """Labeled samples too: ``name{label="x"}`` -> value (the per-model /
    per-bucket families scrape_metrics skips)."""
    host, port = netloc.split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=5)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        lhs, _, value = line.rpartition(" ")
        if not lhs:
            continue
        try:
            out[lhs] = float(value)
        except ValueError:
            pass
    return out


def labeled_family(labeled: Dict[str, float], family: str) -> Dict[str, float]:
    """{label-string: value} for one family's samples."""
    out = {}
    prefix = family + "{"
    for k, v in labeled.items():
        if k.startswith(prefix) and k.endswith("}"):
            out[k[len(prefix):-1]] = v
    return out


def _label_get(labels: str, key: str) -> str:
    import re
    m = re.search(key + r'="([^"]*)"', labels)
    return m.group(1) if m else ""


def per_bucket_padding_rows(labeled: Dict[str, float]) -> List[str]:
    """Markdown rows: per-(model, bucket) real/pad split + padding
    fraction (the aggregate number hides WHERE padded rows go — under a
    cascade the student and flagship fill buckets very differently)."""
    fam = labeled_family(labeled, "dfd_serving_bucket_rows_total")
    acc: Dict[Tuple[str, int], Dict[str, float]] = {}
    for labels, v in fam.items():
        key = (_label_get(labels, "model"),
               int(_label_get(labels, "bucket") or 0))
        acc.setdefault(key, {})[_label_get(labels, "kind")] = v
    rows = ["| model | bucket | real rows | pad rows | padding |",
            "|---|---|---|---|---|"]
    for (model, bucket) in sorted(acc):
        real = acc[(model, bucket)].get("real", 0)
        pad = acc[(model, bucket)].get("pad", 0)
        frac = 100.0 * pad / max(1.0, real + pad)
        rows.append(f"| {model} | {bucket} | {real:.0f} | {pad:.0f} | "
                    f"{frac:.1f}% |")
    return rows if len(rows) > 2 else []


def per_model_rows(labeled: Dict[str, float]) -> List[str]:
    """Markdown rows: per-model request books from the labeled ledger."""
    kinds = ("accepted", "cache_hit", "scored", "failed", "shed",
             "deadline")
    models = set()
    for kind in kinds:
        fam = labeled_family(labeled,
                             f"dfd_serving_model_{kind}_total")
        models.update(_label_get(l, "model") for l in fam)
    if not models:
        return []
    rows = ["| model | accepted | cache_hit | scored | failed | shed | "
            "deadline |",
            "|---|---|---|---|---|---|---|"]
    for model in sorted(models):
        vals = []
        for kind in kinds:
            fam = labeled_family(labeled,
                                 f"dfd_serving_model_{kind}_total")
            vals.append(fam.get(f'model="{model}"', 0))
        rows.append("| " + model + " | " +
                    " | ".join(f"{v:.0f}" for v in vals) + " |")
    return rows


def scrape_metrics(netloc: str) -> Dict[str, float]:
    host, port = netloc.split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=5)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        parts = line.split()
        if len(parts) == 2 and "{" not in parts[0]:
            try:
                out[parts[0]] = float(parts[1])
            except ValueError:
                pass
    return out


# ---------------------------------------------------------------------------
# closed-loop load
# ---------------------------------------------------------------------------

class _Client(threading.Thread):
    """Keep-alive closed-loop client on a raw socket with pre-serialized
    requests — ``http.client``'s object churn would bill ~1 ms/req of this
    2-core box's CPU to the load generator instead of the server."""

    def __init__(self, netloc: str, jpegs: List[bytes], stop: threading.Event,
                 measure_from: float, seed: int,
                 retry_cap_s: float = 2.0,
                 popularity: Optional[np.ndarray] = None):
        super().__init__(daemon=True)
        host, port = netloc.split(":")
        self.addr = (host, int(port))
        self.stop_ev = stop
        self.measure_from = measure_from
        self.retry_cap_s = retry_cap_s
        self.latencies: List[float] = []
        self.statuses: Dict[int, int] = {}
        # pre-serialize one request per source image
        self.requests = []
        for body in jpegs:
            head = (f"POST /score HTTP/1.1\r\nHost: {host}\r\n"
                    f"Content-Type: image/jpeg\r\n"
                    f"Content-Length: {len(body)}\r\n\r\n").encode()
            self.requests.append(head + body)
        rng = np.random.default_rng(seed)
        self.offset = int(rng.integers(0, len(self.requests)))
        # popularity-weighted traffic (the --zipf phase): a seeded
        # pre-drawn schedule per client, cycled — sampling in the hot
        # loop would bill rng time to the server under test
        self.order: Optional[np.ndarray] = None
        if popularity is not None:
            self.order = rng.choice(len(self.requests), size=8192,
                                    p=popularity)

    def _recv_response(self, sock_file) -> Tuple[int, float]:
        """Minimal HTTP/1.1 response read: status + headers +
        Content-Length body; returns (status, retry_after_s or 0)."""
        status_line = sock_file.readline()
        if not status_line:
            raise OSError("connection closed")
        status = int(status_line.split(b" ", 2)[1])
        length = 0
        retry_after = 0.0
        while True:
            line = sock_file.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            if line.lower().startswith(b"content-length:"):
                length = int(line.split(b":", 1)[1])
            elif line.lower().startswith(b"retry-after:"):
                try:
                    retry_after = float(line.split(b":", 1)[1])
                except ValueError:
                    pass
        if length:
            sock_file.read(length)
        return status, retry_after

    def run(self) -> None:
        sock = None
        f = None
        i = self.offset
        consec_shed = 0
        while not self.stop_ev.is_set():
            t0 = time.monotonic()
            retry_after = 0.0
            try:
                if sock is None:
                    sock = socket.create_connection(self.addr, timeout=30)
                    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY,
                                    1)
                    f = sock.makefile("rb")
                idx = (int(self.order[i % len(self.order)])
                       if self.order is not None
                       else i % len(self.requests))
                sock.sendall(self.requests[idx])
                i += 1
                status, retry_after = self._recv_response(f)
            except OSError:
                if sock is not None:
                    sock.close()
                sock = None
                status = -1
            dt = time.monotonic() - t0
            if t0 >= self.measure_from:
                if status == 200:
                    self.latencies.append(dt)
                self.statuses[status] = self.statuses.get(status, 0) + 1
            if status in (429, 503):
                # honor the server's (jittered) Retry-After with capped
                # exponential backoff: repeated sheds double the wait up
                # to the cap instead of hammering a saturated queue
                consec_shed += 1
                base = retry_after if retry_after > 0 else 0.05
                wait = min(self.retry_cap_s,
                           base * (2 ** min(consec_shed - 1, 4)))
                self.stop_ev.wait(wait)
            else:
                consec_shed = 0
        if sock is not None:
            sock.close()


def run_load(netloc: str, jpegs: List[bytes], concurrency: int,
             duration: float, warmup: float,
             retry_cap_s: float = 2.0,
             popularity: Optional[np.ndarray] = None) -> Dict[str, float]:
    stop = threading.Event()
    t_start = time.monotonic()
    measure_from = t_start + warmup
    clients = [_Client(netloc, jpegs, stop, measure_from, seed=c,
                       retry_cap_s=retry_cap_s, popularity=popularity)
               for c in range(concurrency)]
    for c in clients:
        c.start()
    time.sleep(warmup + duration)
    stop.set()
    for c in clients:
        c.join(timeout=10)
    lats = sorted(l for c in clients for l in c.latencies)
    statuses: Dict[int, int] = {}
    for c in clients:
        for s, n in c.statuses.items():
            statuses[s] = statuses.get(s, 0) + n
    n_ok = len(lats)
    if n_ok == 0:
        return {"rps": 0.0, "p50": float("nan"), "p95": float("nan"),
                "p99": float("nan"), "statuses": statuses}

    def pct(p: float) -> float:
        return lats[min(n_ok - 1, int(p / 100.0 * n_ok))] * 1000.0

    return {"rps": n_ok / duration, "p50": pct(50), "p95": pct(95),
            "p99": pct(99), "mean": statistics.fmean(lats) * 1000.0,
            "statuses": statuses}


def engine_closed_loop(args, jpegs: List[bytes], concurrency: int,
                       duration: float, warmup: float) -> Dict[str, float]:
    """The serving subsystem WITHOUT the socket layer: threads preprocess
    + submit + wait against an in-process batcher/engine.  Separates what
    the micro-batcher + bucketed compile cache deliver from what this
    box's python HTTP tax costs (the colocated load generator shares the
    cores with the server, so the HTTP rows under-read on small hosts)."""
    import jax

    from deepfake_detection_tpu.models import create_model, init_model
    from deepfake_detection_tpu.params import (normalize_replicate,
                                               prepare_canvas)
    from deepfake_detection_tpu.serving.batcher import MicroBatcher
    from deepfake_detection_tpu.serving.engine import InferenceEngine
    from deepfake_detection_tpu.serving.metrics import ServingMetrics
    from PIL import Image

    size = args.image_size
    chans = 3 * args.img_num
    buckets = tuple(int(b) for b in args.buckets.split(","))
    model = create_model(args.model, num_classes=2, in_chans=chans)
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, size, size, chans))
    metrics = ServingMetrics()
    engine = InferenceEngine(model, variables, image_size=size,
                             img_num=args.img_num, buckets=buckets,
                             metrics=metrics, wire=args.wire)
    batcher = MicroBatcher(max_batch=buckets[-1],
                           deadline_ms=args.deadline_ms,
                           max_queue=args.max_queue, metrics=metrics)
    engine.start(batcher)
    stop = threading.Event()
    t_start = time.monotonic()
    measure_from = t_start + warmup
    lats_per: List[List[float]] = [[] for _ in range(concurrency)]

    def client(ci: int) -> None:
        i = ci
        while not stop.is_set():
            t0 = time.monotonic()
            img = np.asarray(Image.open(io.BytesIO(
                jpegs[i % len(jpegs)])).convert("RGB"), np.uint8)
            i += 1
            payload = prepare_canvas(img, size)
            if args.wire == "float32":
                payload = normalize_replicate(payload, args.img_num)
            req = batcher.submit(payload, timeout_s=30)
            req.result(timeout=30)
            if t0 >= measure_from:
                lats_per[ci].append(time.monotonic() - t0)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(concurrency)]
    for t in threads:
        t.start()
    time.sleep(warmup + duration)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    engine.stop()
    batcher.close()
    lats = sorted(l for per in lats_per for l in per)
    n = len(lats)

    def pct(p: float) -> float:
        return lats[min(n - 1, int(p / 100.0 * n))] * 1000.0 if n else \
            float("nan")

    return {"rps": n / duration, "p50": pct(50), "p95": pct(95),
            "p99": pct(99), "statuses": {200: n}}


# ---------------------------------------------------------------------------
# baselines
# ---------------------------------------------------------------------------

def warm_sequential_baseline(args, jpegs: List[bytes],
                             n_images: int = 64) -> float:
    """runners/test.py scoring semantics in a warmed process: preprocess +
    batch-1 jitted score per image, one at a time."""
    import jax
    import jax.numpy as jnp

    from deepfake_detection_tpu.models import create_model, init_model
    from deepfake_detection_tpu.params import make_score_fn
    from deepfake_detection_tpu.runners.test import preprocess

    size = args.image_size
    chans = 3 * args.img_num
    model = create_model(args.model, num_classes=2, in_chans=chans)
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, size, size, chans))
    score_fn = make_score_fn(model, variables)
    for d in jpegs[:2]:          # compile + warm
        np.asarray(score_fn(jnp.asarray(
            preprocess(io.BytesIO(d), size, num=args.img_num))))
    t0 = time.monotonic()
    for i in range(n_images):
        d = jpegs[i % len(jpegs)]
        np.asarray(score_fn(jnp.asarray(
            preprocess(io.BytesIO(d), size, num=args.img_num))))
    return n_images / (time.monotonic() - t0)


_COLD_SNIPPET = r"""
import io, sys, time
t0 = time.monotonic()
import numpy as np, jax, jax.numpy as jnp
from deepfake_detection_tpu.models import create_model, init_model
from deepfake_detection_tpu.params import make_score_fn
from deepfake_detection_tpu.runners.test import preprocess
model_name, size, num = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
with open(sys.argv[4], "rb") as f:
    data = f.read()
model = create_model(model_name, num_classes=2, in_chans=3 * num)
variables = init_model(model, jax.random.PRNGKey(0),
                       (1, size, size, 3 * num))
score_fn = make_score_fn(model, variables)
np.asarray(score_fn(jnp.asarray(preprocess(io.BytesIO(data), size,
                                           num=num))))
print(time.monotonic() - t0)
"""


def cold_oneshot_baseline(args, jpeg: bytes) -> Optional[float]:
    """Wall seconds for one image through a FRESH interpreter (the one-shot
    CLI reality): startup + build + compile + score.  Runs against a fixed,
    freshly emptied XLA compile cache dir so it measures the true cold
    path."""
    import shutil
    cold_cache = os.path.join(_REPO, ".jax_cache", "bench_serve_cold")
    shutil.rmtree(cold_cache, ignore_errors=True)
    with tempfile.TemporaryDirectory() as td:
        img = os.path.join(td, "img.jpg")
        with open(img, "wb") as f:
            f.write(jpeg)
        env = dict(os.environ)
        if not args.keep_env:
            env.setdefault("JAX_PLATFORMS", "cpu")
        env["JAX_COMPILATION_CACHE_DIR"] = cold_cache
        try:
            out = subprocess.run(
                [sys.executable, "-c", _COLD_SNIPPET, args.model,
                 str(args.image_size), str(args.img_num), img],
                cwd=_REPO, env=env, capture_output=True, text=True,
                timeout=1800, check=True)
            return float(out.stdout.strip().splitlines()[-1])
        except (subprocess.SubprocessError, ValueError) as e:
            _log(f"cold baseline failed: {e!r}")
            return None


# ---------------------------------------------------------------------------
# cascade matrix (--models/--cascade/--traffic-mix)
# ---------------------------------------------------------------------------

def calibrate_band(args, jpegs: List[bytes]) -> Tuple[float, float]:
    """Suspect band [lo, 1.0] such that ~``--traffic-mix`` of the bench
    traffic clears on the student.

    Synthetic bench traffic has no ground truth, so the escalation
    fraction is dialed in from the student's own score distribution on
    the exact jpeg set the load generator cycles: lo = the traffic-mix
    quantile of the student's fake scores (the server's student is the
    same deterministic seed-0 init, so the in-process replica scores
    identically).  Real deployments pick the band from validation data
    instead — this keeps the measured mix honest on a random init."""
    import io as _io

    import jax
    import jax.numpy as jnp
    from PIL import Image

    from deepfake_detection_tpu.config import parse_model_spec
    from deepfake_detection_tpu.models import create_model, init_model
    from deepfake_detection_tpu.params import (make_score_fn,
                                               normalize_replicate,
                                               prepare_canvas)
    from deepfake_detection_tpu.serving.quant import quantize_tree

    specs = {s["id"]: s for s in (
        parse_model_spec(e, default_size=args.image_size,
                         default_img_num=args.img_num)
        for e in args.models.split(";") if e.strip())}
    spec = specs[args.cascade]
    size, num = spec["size"], spec["img_num"]
    model = create_model(spec["family"], num_classes=2, in_chans=3 * num)
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, size, size, 3 * num))
    if spec["path"]:
        from deepfake_detection_tpu.models.helpers import load_checkpoint
        variables = load_checkpoint(variables, spec["path"], strict=False)
    variables = jax.device_put(quantize_tree(variables, spec["dtype"]))
    # the engine's exact variables-as-argument program (bit-parity
    # contract) — never a re-derived local copy of it
    score_fn = make_score_fn(model, variables)

    x = jnp.asarray(np.stack([
        normalize_replicate(prepare_canvas(np.asarray(
            Image.open(_io.BytesIO(j)).convert("RGB"), np.uint8), size),
            num) for j in jpegs]))
    p_fake = np.asarray(score_fn(x))[:, 0]
    lo = float(np.quantile(p_fake, args.traffic_mix))
    frac = float((p_fake >= lo).mean())
    _log(f"calibrated suspect band [{lo:.4f}, 1.0]: {frac:.0%} of the "
         f"bench traffic escalates (target {1 - args.traffic_mix:.0%})")
    return lo, 1.0


def assert_cascade_books(m: Dict[str, float]) -> None:
    tri = m.get("dfd_serving_cascade_triaged_total", 0)
    clr = m.get("dfd_serving_cascade_cleared_total", 0)
    esc = m.get("dfd_serving_cascade_escalated_total", 0)
    fs = m.get("dfd_serving_cascade_flagship_scored_total", 0)
    ef = m.get("dfd_serving_cascade_escalation_failed_total", 0)
    if tri != clr + esc or esc != fs + ef:
        raise AssertionError(
            f"cascade books do not balance: triaged {tri:.0f} != cleared "
            f"{clr:.0f} + escalated {esc:.0f}, or escalated {esc:.0f} != "
            f"flagship_scored {fs:.0f} + escalation_failed {ef:.0f}")
    _log(f"cascade books balance: {tri:.0f} triaged == {clr:.0f} cleared "
         f"+ {esc:.0f} escalated; {esc:.0f} escalated == {fs:.0f} "
         f"flagship + {ef:.0f} failed")


def run_cascade_phase(args, jpegs: List[bytes],
                      concurrency: int) -> Tuple[dict, Dict[str, float]]:
    """Spawn the two-model cascade server, drive the same closed loop,
    and return (load stats incl. cascade counters, labeled metrics)."""
    lo, hi = calibrate_band(args, jpegs)
    extra = ["--models", args.models, "--cascade", args.cascade,
             "--cascade-low", f"{lo:.6f}", "--cascade-high", f"{hi:.6f}"]
    proc, netloc = spawn_server(args, extra=extra)
    try:
        wait_ready(netloc)
        m0 = scrape_metrics(netloc)
        backend0 = m0.get("dfd_serving_backend_compiles_total", 0)
        compiles0 = m0.get("dfd_serving_compiles_total", 0)
        _log(f"cascade closed loop: concurrency {concurrency}, "
             f"{args.duration:.0f}s (+{args.warmup:.0f}s warmup)")
        r = run_load(netloc, jpegs, concurrency, args.duration,
                     args.warmup, retry_cap_s=args.retry_cap)
        _log(f"  -> {r['rps']:.1f} req/s, p50 {r['p50']:.1f} ms, "
             f"statuses {r['statuses']}")
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            m1 = scrape_metrics(netloc)
            acc = m1.get("dfd_serving_accepted_total", 0)
            resolved = (m1.get("dfd_serving_cache_hit_total", 0) +
                        m1.get("dfd_serving_scored_total", 0) +
                        m1.get("dfd_serving_shed_total", 0) +
                        m1.get("dfd_serving_deadline_total", 0) +
                        m1.get("dfd_serving_failed_total", 0))
            if acc == resolved:
                break
            time.sleep(1.0)
        if acc != resolved:
            raise AssertionError(f"books do not balance after drain: "
                                 f"accepted {acc:.0f} != {resolved:.0f}")
        assert_cascade_books(m1)
        recompiles = (m1.get("dfd_serving_compiles_total", 0) - compiles0)             + (m1.get("dfd_serving_backend_compiles_total", 0) - backend0)
        if recompiles:
            raise AssertionError(f"{recompiles:+.0f} recompiles during "
                                 f"the cascade phase (must be zero)")
        _log("cascade phase: zero post-warmup recompiles, books balanced")
        labeled = scrape_metrics_labeled(netloc)
        r["cascade"] = {k.rsplit("_total", 1)[0].split("cascade_")[-1]: v
                        for k, v in m1.items()
                        if k.startswith("dfd_serving_cascade_")}
        r["band"] = (lo, hi)
        return r, labeled
    finally:
        _terminate_proc(proc)


def _terminate_proc(proc: subprocess.Popen) -> None:
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


# ---------------------------------------------------------------------------
# verdict-cache Zipf phase (ISSUE 17): viral traffic, cache on vs off
# ---------------------------------------------------------------------------

def zipf_popularity(n: int, s: float) -> np.ndarray:
    """Zipf(s) rank-popularity over ``n`` items (rank 1 = most viral)."""
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** -s
    return w / w.sum()


def _drain_serving_books(netloc: str) -> Dict[str, float]:
    """Wait for the serving ledger to settle, then assert it EXACTLY:
    accepted == cache_hit + scored + shed + deadline + failed."""
    deadline = time.monotonic() + 30.0
    while True:
        m = scrape_metrics(netloc)
        acc = m.get("dfd_serving_accepted_total", 0)
        resolved = (m.get("dfd_serving_cache_hit_total", 0) +
                    m.get("dfd_serving_scored_total", 0) +
                    m.get("dfd_serving_shed_total", 0) +
                    m.get("dfd_serving_deadline_total", 0) +
                    m.get("dfd_serving_failed_total", 0))
        if acc == resolved or time.monotonic() > deadline:
            break
        time.sleep(0.5)
    if acc != resolved:
        raise AssertionError(
            f"serving books do not balance after drain: accepted "
            f"{acc:.0f} != cache_hit "
            f"{m.get('dfd_serving_cache_hit_total', 0):.0f} + scored "
            f"{m.get('dfd_serving_scored_total', 0):.0f} + shed "
            f"{m.get('dfd_serving_shed_total', 0):.0f} + deadline "
            f"{m.get('dfd_serving_deadline_total', 0):.0f} + failed "
            f"{m.get('dfd_serving_failed_total', 0):.0f}")
    return m


def _sequential_p50_ms(netloc: str, body: bytes, n: int = 40) -> float:
    """Median latency of ``n`` sequential uncontended /score requests of
    ONE image (2 warm requests discarded) — the direct hit-latency probe:
    after the load phase the most-popular clip is certainly cached."""
    host, port = netloc.split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    lats = []
    for i in range(n + 2):
        t0 = time.monotonic()
        conn.request("POST", "/score", body,
                     {"Content-Type": "image/jpeg"})
        resp = conn.getresponse()
        resp.read()
        if i >= 2 and resp.status == 200:
            lats.append(time.monotonic() - t0)
    conn.close()
    lats.sort()
    return lats[len(lats) // 2] * 1000.0 if lats else float("nan")


def run_zipf_phase(args) -> List[str]:
    """ISSUE 17: closed-loop Zipf(s) viral traffic, cache-off vs
    cache-on, SAME seeded schedule both phases.

    The cache capacity is deliberately smaller than the distinct-clip
    count, so the hit rate is the LRU keeping the popular head resident
    — not a degenerate everything-fits cache.  Asserted per phase: exact
    serving books (accepted == cache_hit + scored + shed + deadline +
    failed) and zero post-warmup recompiles (a hit never enters a
    bucket).  The pre-registered heavy-flagship bar is >= 3x effective
    req/s at s=1.1; auto (<=0) asserts strict ordering on shared-core
    boxes where the colocated load generator caps the ratio."""
    s = args.zipf
    n = args.zipf_clips
    cap = args.zipf_cache_entries
    if cap >= n:
        raise SystemExit(f"--zipf-cache-entries {cap} must be < "
                         f"--zipf-clips {n} (an everything-fits cache "
                         f"measures nothing)")
    bar = args.zipf_bar if args.zipf_bar > 0 else 1.05
    concurrency = max(int(x) for x in args.concurrency.split(","))
    jpegs = make_jpegs(n, args.src_size, seed=17)
    pop = zipf_popularity(n, s)
    _log(f"zipf phase: s={s}, {n} distinct clips, cache capacity {cap} "
         f"(top-{cap} popularity mass {pop[:cap].sum():.0%}), "
         f"concurrency {concurrency}")
    results: Dict[str, dict] = {}
    for mode in ("off", "on"):
        extra = [] if mode == "off" else \
            ["--cache-entries", str(cap)]
        proc, netloc = spawn_server(args, extra=extra)
        try:
            wait_ready(netloc)
            m0 = scrape_metrics(netloc)
            compiles0 = m0.get("dfd_serving_compiles_total", 0)
            backend0 = m0.get("dfd_serving_backend_compiles_total", 0)
            _log(f"zipf closed loop [cache {mode}]: {args.duration:.0f}s "
                 f"(+{args.warmup:.0f}s warmup)")
            r = run_load(netloc, jpegs, concurrency, args.duration,
                         args.warmup, retry_cap_s=args.retry_cap,
                         popularity=pop)
            m1 = _drain_serving_books(netloc)
            recompiles = ((m1.get("dfd_serving_compiles_total", 0) -
                           compiles0) +
                          (m1.get("dfd_serving_backend_compiles_total",
                                  0) - backend0))
            if recompiles:
                raise AssertionError(
                    f"[cache {mode}] {recompiles:+.0f} recompiles during "
                    f"the zipf phase (must be zero)")
            r["books"] = {k: m1.get(f"dfd_serving_{k}_total", 0)
                          for k in ("accepted", "cache_hit", "scored",
                                    "shed", "deadline", "failed")}
            acc = max(1.0, r["books"]["accepted"])
            r["hit_rate"] = r["books"]["cache_hit"] / acc
            # uncontended sequential probe of the most-popular clip:
            # a guaranteed hit on the cache-on server, a fresh score on
            # the cache-off one (the direct hit-vs-miss latency read)
            r["probe_p50"] = _sequential_p50_ms(netloc, jpegs[0])
            _log(f"  -> {r['rps']:.1f} req/s, p50 {r['p50']:.1f} ms, "
                 f"hit rate {r['hit_rate']:.0%}, sequential probe "
                 f"{r['probe_p50']:.2f} ms, statuses {r['statuses']}, "
                 f"books {r['books']}")
            results[mode] = r
        finally:
            _terminate_proc(proc)
    ratio = results["on"]["rps"] / max(1e-9, results["off"]["rps"])
    _log(f"zipf s={s}: cache-on {results['on']['rps']:.1f} vs cache-off "
         f"{results['off']['rps']:.1f} req/s = {ratio:.2f}x (bar "
         f"{bar:.2f}x); hit probe {results['on']['probe_p50']:.2f} ms "
         f"vs miss probe {results['off']['probe_p50']:.2f} ms")
    if ratio < bar:
        raise AssertionError(
            f"zipf bar missed: cache-on is {ratio:.2f}x cache-off "
            f"effective req/s, bar is {bar:.2f}x")

    lines = []
    lines.append(
        f"**Verdict cache (ISSUE 17)** — closed-loop Zipf(s={s}) viral "
        f"traffic over {n} distinct clips, {concurrency} keep-alive "
        f"clients, {args.duration:.0f}s measured per phase, cache "
        f"capacity {cap} entries (top-{cap} popularity mass "
        f"{pop[:cap].sum():.0%} — the LRU must keep the viral head "
        f"resident, nothing fits whole).  Exact serving books and zero "
        f"post-warmup recompiles asserted both phases; same seeded "
        f"request schedule both phases.")
    lines.append("")
    lines.append("| verdict cache | effective req/s | vs off | p50 (ms) "
                 "| p95 (ms) | hit rate | sequential probe (ms) | books "
                 "(acc=hit+scored+shed+ddl+fail) |")
    lines.append("|---|---|---|---|---|---|---|---|")
    for mode in ("off", "on"):
        r = results[mode]
        b = r["books"]
        bk = (f"{b['accepted']:.0f}={b['cache_hit']:.0f}+"
              f"{b['scored']:.0f}+{b['shed']:.0f}+{b['deadline']:.0f}+"
              f"{b['failed']:.0f}")
        rel = f"{r['rps'] / max(1e-9, results['off']['rps']):.2f}×"
        lines.append(f"| {mode} | {r['rps']:.1f} | {rel} | "
                     f"{r['p50']:.1f} | {r['p95']:.1f} | "
                     f"{r['hit_rate']:.0%} | {r['probe_p50']:.2f} | "
                     f"{bk} |")
    lines.append("")
    lines.append(
        f"The sequential probe re-scores the single most-viral clip "
        f"uncontended: {results['on']['probe_p50']:.2f} ms served from "
        f"the cache vs {results['off']['probe_p50']:.2f} ms through the "
        f"model — a hit costs decode+canonicalize+hash only, never a "
        f"bucket slot.")
    return lines


# ---------------------------------------------------------------------------
# fleet matrix (--replicas): N serve replicas behind runners/router.py
# ---------------------------------------------------------------------------

def spawn_router(replica_netlocs: List[str], data_plane: str = "evloop"
                 ) -> Tuple[subprocess.Popen, str]:
    """Spawn the fleet router attached to already-running replicas."""
    port = free_port()
    cmd = [sys.executable, "-m", "deepfake_detection_tpu.runners.router",
           "--port", str(port),
           "--replicas", ",".join(replica_netlocs),
           "--data-plane", data_plane,
           "--scrape-interval-s", "0.2", "--health-fail-after", "2"]
    _log("spawning router: " + " ".join(cmd))
    proc = subprocess.Popen(cmd, cwd=_REPO, env=dict(os.environ),
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    return proc, f"127.0.0.1:{port}"


def wait_fleet_ready(router_netloc: str, n: int,
                     timeout: float = 120.0) -> None:
    """Poll the router's /readyz JSON until all ``n`` replicas are
    healthy AND ready (the scraper has seen every /readyz go 200)."""
    import json as _json
    host, port = router_netloc.split(":")
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        try:
            conn = http.client.HTTPConnection(host, int(port), timeout=2)
            conn.request("GET", "/readyz")
            resp = conn.getresponse()
            body = resp.read()
            if resp.status == 200:
                counts = _json.loads(body).get("counts", {})
                if counts.get("ready", 0) >= n:
                    _log(f"fleet ready ({n} replicas) after "
                         f"{time.monotonic() - t0:.1f}s")
                    return
        except (OSError, ValueError):
            pass
        time.sleep(0.2)
    raise TimeoutError(f"fleet at {router_netloc} not ready ({n} "
                       f"replicas) within {timeout}s")


def assert_router_books(m: Dict[str, float]) -> None:
    routed = m.get("dfd_router_routed_total", 0)
    resolved = (m.get("dfd_router_cache_hit_total", 0) +
                m.get("dfd_router_forwarded_total", 0) +
                m.get("dfd_router_migrated_total", 0) +
                m.get("dfd_router_shed_total", 0) +
                m.get("dfd_router_failed_total", 0))
    if routed != resolved:
        raise AssertionError(
            f"router books do not balance: routed {routed:.0f} != "
            f"cache_hit {m.get('dfd_router_cache_hit_total', 0):.0f} + "
            f"forwarded {m.get('dfd_router_forwarded_total', 0):.0f} + "
            f"migrated {m.get('dfd_router_migrated_total', 0):.0f} + "
            f"shed {m.get('dfd_router_shed_total', 0):.0f} + "
            f"failed {m.get('dfd_router_failed_total', 0):.0f}")
    _log(f"router books balance: routed {routed:.0f} == resolved "
         f"{resolved:.0f}")


def run_fleet_phase(args, jpegs: List[bytes], n: int,
                    concurrency: int) -> dict:
    """One fleet size: N replicas + router, closed loop through the
    router, books + zero-recompile asserts, per-replica spread."""
    replicas = []
    router_proc = None
    try:
        for _ in range(n):
            replicas.append(spawn_server(args))
        for _, netloc in replicas:
            wait_ready(netloc)
        router_proc, router_netloc = spawn_router(
            [netloc for _, netloc in replicas],
            data_plane=args.data_plane)
        wait_fleet_ready(router_netloc, n)
        compiles0 = []
        for _, netloc in replicas:
            m = scrape_metrics(netloc)
            compiles0.append(
                m.get("dfd_serving_backend_compiles_total", 0))
        _log(f"fleet closed loop: {n} replica(s), concurrency "
             f"{concurrency}, {args.duration:.0f}s "
             f"(+{args.warmup:.0f}s warmup)")
        r = run_load(router_netloc, jpegs, concurrency, args.duration,
                     args.warmup, retry_cap_s=args.retry_cap)
        _log(f"  -> {r['rps']:.1f} req/s, p50 {r['p50']:.1f} ms, "
             f"statuses {r['statuses']}")
        # drain then assert the router books exactly
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            rm = scrape_metrics(router_netloc)
            routed = rm.get("dfd_router_routed_total", 0)
            resolved = (rm.get("dfd_router_cache_hit_total", 0) +
                        rm.get("dfd_router_forwarded_total", 0) +
                        rm.get("dfd_router_migrated_total", 0) +
                        rm.get("dfd_router_shed_total", 0) +
                        rm.get("dfd_router_failed_total", 0))
            if routed == resolved:
                break
            time.sleep(1.0)
        assert_router_books(rm)
        # the aggregate re-export must carry every replica's catalog
        labeled = scrape_metrics_labeled(router_netloc)
        fam = labeled_family(labeled, "dfd_serving_scored_total")
        if len(fam) != n:
            raise AssertionError(
                f"aggregate /metrics re-exports {len(fam)} replica "
                f"catalog(s), expected {n}: {sorted(fam)}")
        spread = labeled_family(labeled, "dfd_router_replica_forwarded_total")
        # zero recompiles on every replica across the load phase
        for (_, netloc), c0 in zip(replicas, compiles0):
            m = scrape_metrics(netloc)
            c1 = m.get("dfd_serving_backend_compiles_total", 0)
            if c1 != c0:
                raise AssertionError(
                    f"replica {netloc}: {c1 - c0:+.0f} backend "
                    f"recompiles during the fleet phase")
        r["replicas"] = n
        r["books"] = {k.rsplit("_total", 1)[0].split("dfd_router_")[-1]: v
                      for k, v in rm.items()
                      if k.startswith("dfd_router_") and
                      k.endswith("_total")}
        r["spread"] = {k: v for k, v in sorted(spread.items())}
        return r
    finally:
        if router_proc is not None:
            _terminate_proc(router_proc)
        for proc, _ in replicas:
            _terminate_proc(proc)


# ---------------------------------------------------------------------------
# relay-ceiling phase (ISSUE 16): pure router relay rate per data plane
# ---------------------------------------------------------------------------

_STUB_SCORE = b'{"p_fake": 0.5, "label": "real", "model": "stub"}'
#: STATIC exposition: the scraper re-exports this text verbatim under a
#: replica= label, so serving it byte-stable makes the replica-labeled
#: re-export lines comparable byte-for-byte across both plane runs
_STUB_EXPO = ("# HELP dfd_serving_scored_total Requests scored\n"
              "# TYPE dfd_serving_scored_total counter\n"
              "dfd_serving_scored_total 0\n"
              "# HELP dfd_serving_inflight In-flight requests\n"
              "# TYPE dfd_serving_inflight gauge\n"
              "dfd_serving_inflight 0\n").encode()


def _start_stub_upstreams(n: int) -> Tuple[list, List[str]]:
    """``n`` instant in-process replica stand-ins: /readyz + the static
    /metrics exposition + /score answered from memory.  Takes the model
    (and every other subprocess) out of the measurement so the phase
    reads pure router relay rate."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _StubHandler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        disable_nagle_algorithm = True    # head+body are separate sends;
        # Nagle against the router's delayed ACK turns each relay into
        # a ~40 ms round trip and the phase stops measuring the router

        def log_message(self, *a):             # noqa: D102
            pass

        def _reply(self, body: bytes, ctype: str) -> None:
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):                      # noqa: N802
            if self.path == "/readyz":
                self._reply(b'{"ready": true}', "application/json")
            else:                              # /metrics, /healthz
                self._reply(_STUB_EXPO, "text/plain; version=0.0.4")

        def do_POST(self):                     # noqa: N802
            length = int(self.headers.get("Content-Length", 0) or 0)
            if length:
                self.rfile.read(length)
            self._reply(_STUB_SCORE, "application/json")

    stubs = []
    for _ in range(n):
        srv = ThreadingHTTPServer(("127.0.0.1", 0), _StubHandler)
        threading.Thread(target=srv.serve_forever,
                         kwargs={"poll_interval": 0.1},
                         daemon=True).start()
        stubs.append(srv)
    return stubs, [f"127.0.0.1:{s.server_address[1]}" for s in stubs]


def _replica_reexport_lines(text: str) -> List[str]:
    """The replica-labeled re-export samples of one aggregate /metrics
    document, router-side families excluded (their values legitimately
    differ between plane runs; the re-exported replica catalogs must
    not)."""
    return [line for line in text.splitlines()
            if 'replica="' in line
            and not line.startswith("dfd_router_")]


def _proc_cpu_s(pid: int) -> float:
    """utime+stime of *pid* in seconds (/proc/<pid>/stat).  The control
    that isolates the router's own cost: on a box where the load
    generator and stubs share cores with the router, wall-clock relays/s
    under-reads the plane difference — CPU charged to the router process
    per relay does not."""
    try:
        with open("/proc/%d/stat" % pid, "rb") as f:
            rest = f.read().split(b") ", 1)[1].split()
        return (int(rest[11]) + int(rest[12])) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return float("nan")


def run_relay_ceiling(args) -> List[str]:
    """ISSUE 16 pre-registered bar: the evloop data plane must relay
    >= ``--relay-bar``x the threads plane's req/s against instant stub
    upstreams, with exact router books and a byte-identical
    replica-labeled re-export, measured in the SAME phase.

    The stubs persist across both plane runs (same ports, same static
    exposition), so any re-export difference is the router's doing."""
    duration = args.relay_duration
    warmup = 0.5 if args.smoke else 1.5
    concurrency = args.relay_concurrency
    bar = args.relay_bar
    if bar <= 0:
        # auto: the ISSUE 16 pre-registered bar is 5.0x wall-clock, but
        # on a shared-core box the colocated client+stub harness caps the
        # achievable wall ratio regardless of router cost (see the SERVE
        # bench notes) — auto asserts the plane ordering (evloop strictly
        # faster); pass --relay-bar 5 to demand the pre-registered bar
        bar = 1.05
    stubs, netlocs = _start_stub_upstreams(2)
    body = b"\x89" * 64           # opaque payload; stubs never decode it
    results: Dict[str, dict] = {}
    books: Dict[str, Dict[str, float]] = {}
    reexports: Dict[str, List[str]] = {}
    try:
        for plane in ("threads", "evloop"):
            proc, router_netloc = spawn_router(netlocs, data_plane=plane)
            try:
                wait_fleet_ready(router_netloc, 2)
                _log(f"relay ceiling [{plane}]: concurrency "
                     f"{concurrency}, {duration:.0f}s "
                     f"(+{warmup:.1f}s warmup)")
                rm0 = scrape_metrics(router_netloc)
                cpu0 = _proc_cpu_s(proc.pid)
                r = run_load(router_netloc, [body], concurrency,
                             duration, warmup,
                             retry_cap_s=args.retry_cap)
                cpu1 = _proc_cpu_s(proc.pid)
                relayed = (scrape_metrics(router_netloc).get(
                    "dfd_router_forwarded_total", 0) -
                    rm0.get("dfd_router_forwarded_total", 0))
                r["cpu_us"] = (cpu1 - cpu0) * 1e6 / max(1.0, relayed)
                _log(f"  -> {r['rps']:.0f} relays/s, p50 "
                     f"{r['p50']:.2f} ms, router CPU "
                     f"{r['cpu_us']:.0f} us/relay, statuses "
                     f"{r['statuses']}")
                bad = {s: c for s, c in r["statuses"].items() if s != 200}
                if bad:
                    raise AssertionError(
                        f"[{plane}] non-200 responses against instant "
                        f"stubs: {bad}")
                deadline = time.monotonic() + 15.0
                while time.monotonic() < deadline:
                    rm = scrape_metrics(router_netloc)
                    if rm.get("dfd_router_routed_total", 0) == (
                            rm.get("dfd_router_cache_hit_total", 0) +
                            rm.get("dfd_router_forwarded_total", 0) +
                            rm.get("dfd_router_migrated_total", 0) +
                            rm.get("dfd_router_shed_total", 0) +
                            rm.get("dfd_router_failed_total", 0)):
                        break
                    time.sleep(0.2)
                assert_router_books(rm)
                host, port = router_netloc.split(":")
                conn = http.client.HTTPConnection(host, int(port),
                                                  timeout=5)
                conn.request("GET", "/metrics")
                text = conn.getresponse().read().decode()
                conn.close()
                catalogs = labeled_family(
                    scrape_metrics_labeled(router_netloc),
                    "dfd_serving_scored_total")
                if len(catalogs) != 2:
                    raise AssertionError(
                        f"[{plane}] aggregate /metrics re-exports "
                        f"{len(catalogs)} replica catalog(s), expected "
                        f"2: {sorted(catalogs)}")
                results[plane] = r
                books[plane] = {
                    k.rsplit("_total", 1)[0].split("dfd_router_")[-1]: v
                    for k, v in rm.items()
                    if k.startswith("dfd_router_") and
                    k.endswith("_total")}
                reexports[plane] = _replica_reexport_lines(text)
            finally:
                _terminate_proc(proc)
    finally:
        for s in stubs:
            s.shutdown()
            s.server_close()
    if reexports["threads"] != reexports["evloop"]:
        import difflib
        diff = "\n".join(difflib.unified_diff(
            reexports["threads"], reexports["evloop"],
            "threads", "evloop", lineterm=""))
        raise AssertionError(
            f"replica-labeled re-export differs between planes:\n{diff}")
    _log(f"re-export byte-identical across planes "
         f"({len(reexports['evloop'])} replica-labeled lines)")
    ratio = results["evloop"]["rps"] / max(1e-9, results["threads"]["rps"])
    cpu_ratio = (results["threads"]["cpu_us"] /
                 max(1e-9, results["evloop"]["cpu_us"]))
    _log(f"relay ceiling: evloop {results['evloop']['rps']:.0f} vs "
         f"threads {results['threads']['rps']:.0f} relays/s = "
         f"{ratio:.2f}x wall (bar {bar:.2f}x); router CPU/relay "
         f"{results['threads']['cpu_us']:.0f} -> "
         f"{results['evloop']['cpu_us']:.0f} us = {cpu_ratio:.2f}x "
         f"cheaper")
    if ratio < bar:
        raise AssertionError(
            f"relay-ceiling bar missed: evloop is {ratio:.2f}x the "
            f"threads plane, bar is {bar:.1f}x")

    lines = []
    lines.append(f"**Relay ceiling (ISSUE 16)** — pure router relay "
                 f"rate per data plane: 2 instant in-process stub "
                 f"upstreams, {concurrency} keep-alive raw-socket "
                 f"clients, {len(body)} B `POST /score` bodies, "
                 f"{duration:.0f}s measured on {os.cpu_count()} CPU "
                 f"core(s).  Exact router books and a byte-identical "
                 f"replica-labeled re-export asserted in the same "
                 f"phase.")
    lines.append("")
    lines.append("| data plane | relays/s | vs threads | p50 (ms) | "
                 "p95 (ms) | p99 (ms) | router CPU µs/relay | "
                 "router books (routed=fwd+mig+shed+fail) |")
    lines.append("|---|---|---|---|---|---|---|---|")
    for plane in ("threads", "evloop"):
        r, b = results[plane], books[plane]
        rel = (f"{r['rps'] / max(1e-9, results['threads']['rps']):.2f}×")
        bk = (f"{b.get('routed', 0):.0f}={b.get('forwarded', 0):.0f}+"
              f"{b.get('migrated', 0):.0f}+{b.get('shed', 0):.0f}+"
              f"{b.get('failed', 0):.0f}")
        lines.append(f"| {plane} | {r['rps']:.0f} | {rel} | "
                     f"{r['p50']:.2f} | {r['p95']:.2f} | "
                     f"{r['p99']:.2f} | {r['cpu_us']:.0f} | {bk} |")
    lines.append("")
    lines.append(f"Router CPU per relay (utime+stime of the router "
                 f"process across the load window, `/proc/<pid>/stat`) "
                 f"is the control that survives core sharing: the "
                 f"evloop plane spends {cpu_ratio:.2f}× less router CPU "
                 f"per relay than the threads plane.")
    return lines


# ---------------------------------------------------------------------------
# elastic autoscale phase (ISSUE 18): spiky load, measured time-to-scale
# ---------------------------------------------------------------------------

class _ElasticPoster(threading.Thread):
    """Closed-loop /score poster for the elastic phase: keeps posting
    until told to stop (the spike has no fixed duration — it ends when
    the fleet has scaled), records every status for the
    zero-client-visible-failures assert."""

    def __init__(self, netloc: str, jpegs: List[bytes],
                 stop: threading.Event, seed: int):
        super().__init__(daemon=True)
        host, port = netloc.split(":")
        self.host, self.port = host, int(port)
        self.jpegs = jpegs
        self.stop_ev = stop
        self.seed = seed
        self.statuses: Dict[int, int] = {}

    def run(self) -> None:
        conn = None
        i = self.seed
        while not self.stop_ev.is_set():
            try:
                if conn is None:
                    conn = http.client.HTTPConnection(
                        self.host, self.port, timeout=60)
                body = self.jpegs[i % len(self.jpegs)]
                i += 1
                conn.request("POST", "/score", body,
                             {"Content-Type": "image/jpeg"})
                resp = conn.getresponse()
                resp.read()
                status = resp.status
            except OSError:
                if conn is not None:
                    conn.close()
                conn = None
                status = -1
            self.statuses[status] = self.statuses.get(status, 0) + 1
            if status in (429, 503):
                self.stop_ev.wait(0.05)
        if conn is not None:
            conn.close()


def _spawn_elastic_router(args, trace_path: str
                          ) -> Tuple[subprocess.Popen, str]:
    """Router that OWNS its fleet: --spawn 1 cold replica plus the SLO
    autoscaler armed to grow to 2.  The breach line is per-replica
    queue depth (deterministic under a closed-loop CPU spike, unlike a
    wall-clock p99 line); the p99 line is parked out of reach."""
    port = free_port()
    replica_args = (f"--model {args.model} --image-size "
                    f"{args.image_size} --img-num {args.img_num} "
                    f"--buckets 1,4 --batch-deadline-ms 5 "
                    f"--max-queue 64")
    if args.single_thread_xla:
        replica_args += " --single-thread-xla"
    cmd = [sys.executable, "-m", "deepfake_detection_tpu.runners.router",
           "--port", str(port),
           "--spawn", "1", "--replica-args", replica_args,
           "--data-plane", args.data_plane,
           "--scrape-interval-s", "0.2", "--health-fail-after", "2",
           "--autoscale", "--min-replicas", "1", "--max-replicas", "2",
           "--autoscale-interval-s", "0.5",
           "--slo-p99-ms", "100000",
           "--autoscale-depth-high", "2", "--autoscale-depth-low", "1",
           "--autoscale-up-samples", "2", "--autoscale-down-samples", "4",
           "--autoscale-up-cooldown-s", "2",
           "--autoscale-down-cooldown-s", "2",
           "--autoscale-trace", trace_path]
    env = dict(os.environ)
    if not args.keep_env:
        env.setdefault("JAX_PLATFORMS", "cpu")
    _log("spawning elastic router: " + " ".join(cmd))
    proc = subprocess.Popen(cmd, cwd=_REPO, env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    return proc, f"127.0.0.1:{port}"


def _wait_metric(netloc: str, probe, what: str,
                 timeout: float = 120.0) -> float:
    """Poll /metrics until ``probe(m)`` is true; returns seconds waited."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        try:
            if probe(scrape_metrics(netloc)):
                return time.monotonic() - t0
        except OSError:
            pass
        time.sleep(0.1)
    raise TimeoutError(f"{what} not observed within {timeout}s")


def run_elastic_phase(args) -> List[str]:
    """ISSUE 18: the spiky load curve.  One cold replica behind the
    autoscaling router; a closed-loop spike breaches the depth line and
    the phase MEASURES the three transitions that define elasticity:

    * spike → acted scale-up decision (``autoscale_up_total``),
    * spike → second replica actually serving (router /readyz count —
      includes the child's full cold start: spawn + import + compile),
    * load off → drain-first retirement (``replicas_retired_total``).

    Exact router books and a bit-exact decision-trace replay
    (``fleet.autoscaler.replay_trace``) are asserted in the same run."""
    jpegs = make_jpegs(16, args.src_size)
    trace_path = os.path.join(
        tempfile.mkdtemp(prefix="bench-elastic-"), "autoscale.jsonl")
    proc, netloc = _spawn_elastic_router(args, trace_path)
    stop = threading.Event()
    posters: List[_ElasticPoster] = []
    try:
        t_cold0 = time.monotonic()
        wait_fleet_ready(netloc, 1, timeout=900.0)
        warm_s = time.monotonic() - t_cold0
        # settle a few idle control ticks first: the scale-up timing
        # below must start from a quiescent policy, not mid-startup
        time.sleep(2.0)
        m0 = scrape_metrics(netloc)
        if m0.get("dfd_router_autoscale_up_total", 0):
            raise AssertionError("scale-up before any load was offered")

        _log(f"spike: {args.elastic_posters} closed-loop posters")
        t_spike = time.monotonic()
        posters = [_ElasticPoster(netloc, jpegs, stop, seed=i)
                   for i in range(args.elastic_posters)]
        for p in posters:
            p.start()
        decision_s = _wait_metric(
            netloc,
            lambda m: m.get("dfd_router_autoscale_up_total", 0) >= 1,
            "scale-up decision", timeout=60.0)
        _log(f"scale-up decided {decision_s:.2f}s after the spike")
        wait_fleet_ready(netloc, 2, timeout=900.0)
        capacity_s = time.monotonic() - t_spike
        _log(f"second replica serving {capacity_s:.2f}s after the spike")
        # hold the spike briefly over the grown fleet, then drop it
        time.sleep(args.elastic_hold)
        stop.set()
        for p in posters:
            p.join(timeout=30)
        scale_in_s = _wait_metric(
            netloc,
            lambda m: m.get("dfd_router_replicas_retired_total", 0) >= 1,
            "drain-first retirement", timeout=120.0)
        _log(f"scale-in retired a replica {scale_in_s:.2f}s after "
             f"load off")
        wait_fleet_ready(netloc, 1, timeout=60.0)

        # exact books after everything drains, and no client ever saw a
        # connection error or 5xx other than a shed 503
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            m = scrape_metrics(netloc)
            if m.get("dfd_router_routed_total", 0) == (
                    m.get("dfd_router_cache_hit_total", 0) +
                    m.get("dfd_router_forwarded_total", 0) +
                    m.get("dfd_router_migrated_total", 0) +
                    m.get("dfd_router_shed_total", 0) +
                    m.get("dfd_router_failed_total", 0)):
                break
            time.sleep(0.5)
        assert_router_books(m)
        statuses: Dict[int, int] = {}
        for p in posters:
            for s, c in p.statuses.items():
                statuses[s] = statuses.get(s, 0) + c
        bad = {s: c for s, c in statuses.items()
               if s not in (200, 429, 503)}
        if bad:
            raise AssertionError(
                f"client-visible failures through the transitions: "
                f"{bad} (statuses {statuses})")
        spawned = m.get("dfd_router_replicas_spawned_total", 0)
        retired = m.get("dfd_router_replicas_retired_total", 0)
        killed = m.get("dfd_router_replicas_killed_total", 0)
        alive = m.get("dfd_router_ready_replicas", 0) + \
            m.get("dfd_router_warming_replicas", 0)
        if spawned != retired + killed + alive:
            raise AssertionError(
                f"replica books do not balance: spawned {spawned:.0f} "
                f"!= retired {retired:.0f} + killed {killed:.0f} + "
                f"alive {alive:.0f}")
        _log(f"replica books balance: spawned {spawned:.0f} == retired "
             f"{retired:.0f} + killed {killed:.0f} + alive {alive:.0f}")
    finally:
        stop.set()
        _terminate_proc(proc)

    # the decision trace must replay bit-exactly through a fresh policy
    from deepfake_detection_tpu.fleet.autoscaler import replay_trace
    rep = replay_trace(trace_path)
    if not rep["match"]:
        raise AssertionError(
            f"decision-trace replay diverged: {rep['mismatches'][:3]}")
    _log(f"decision trace replays bit-exactly ({rep['n']} ticks)")

    lines = []
    lines.append(f"**Elastic autoscale (ISSUE 18)** — 1 cold replica "
                 f"behind the autoscaling router "
                 f"(`--min-replicas 1 --max-replicas 2`, depth line 2, "
                 f"0.5s control ticks), {args.elastic_posters} "
                 f"closed-loop posters spiking `{args.model}` @ "
                 f"{args.image_size}px on {os.cpu_count()} CPU "
                 f"core(s).  Exact router books, zero client-visible "
                 f"failures and a bit-exact decision-trace replay "
                 f"asserted in the same run.")
    lines.append("")
    lines.append("| transition | time |")
    lines.append("|---|---|")
    lines.append(f"| cold start → first replica serving | "
                 f"{warm_s:.1f}s |")
    lines.append(f"| spike → acted scale-up decision | "
                 f"{decision_s:.1f}s |")
    lines.append(f"| spike → second replica serving (incl. child cold "
                 f"start) | {capacity_s:.1f}s |")
    lines.append(f"| load off → drain-first retirement | "
                 f"{scale_in_s:.1f}s |")
    lines.append(f"| decision-trace replay | bit-exact, {rep['n']} "
                 f"ticks |")
    lines.append("")
    lines.append(f"Statuses through every transition: "
                 f"{dict(sorted(statuses.items()))} — sheds (503/429) "
                 f"are the breach signal doing its job; no connection "
                 f"error or unexpected 5xx ever reached a client.")
    return lines


# ---------------------------------------------------------------------------
# cold-start phase (ISSUE 19): persistent AOT store + standby promotion
# ---------------------------------------------------------------------------

_WARM_STAGES = ("spawn", "import", "params_load", "compile", "warm",
                "ready")


def _write_bench_checkpoint(args, path: str) -> None:
    """A real checkpoint for the bench model so the replicas take the
    skeleton params-load fast path (eval_shape + strict load — no init
    jit), same as production scale-ups."""
    import jax

    from deepfake_detection_tpu.models import create_model, init_model
    from deepfake_detection_tpu.models.helpers import save_model_checkpoint
    chans = 3 * args.img_num
    model = create_model(args.model, num_classes=2, in_chans=chans)
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, args.image_size, args.image_size, chans))
    save_model_checkpoint(path, variables)
    _log(f"wrote bench checkpoint ({chans} chans) to {path}")


def _warmup_breakdown(labeled: Dict[str, float]) -> Dict[str, float]:
    fam = labeled_family(labeled, "dfd_serving_warmup_seconds")
    out = {}
    for stage in _WARM_STAGES:
        out[stage] = fam.get(f'stage="{stage}"', 0.0)
    return out


def _coldstart_once(args, ckpt: str, store: str, label: str
                    ) -> Dict[str, float]:
    """One fresh serve process over the store: wall to /readyz 200, the
    per-stage breakdown and the warm-start books, plus a scored request
    as proof the warm path actually serves."""
    proc, netloc = spawn_server(
        args, extra=["--model-path", ckpt, "--warmstart-dir", store],
        env_extra={"DFD_SPAWN_T": repr(time.time())})
    try:
        t0 = time.monotonic()
        wait_ready(netloc, timeout=900.0)
        observed_s = time.monotonic() - t0
        labeled = scrape_metrics_labeled(netloc)
        m = scrape_metrics(netloc)
        host, port = netloc.split(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=60)
        conn.request("POST", "/score", make_jpegs(1, args.src_size)[0],
                     {"Content-Type": "image/jpeg"})
        resp = conn.getresponse()
        body = resp.read()
        conn.close()
        if resp.status != 200:
            raise AssertionError(
                f"{label}: /score returned {resp.status}: {body[:200]}")
        stages = _warmup_breakdown(labeled)
        out = {
            "observed_s": observed_s,
            "ready_s": stages["ready"],
            "compiles": m.get("dfd_serving_backend_compiles_total", 0),
            "hits": m.get("dfd_serving_warmstart_hits_total", 0),
            "misses": m.get("dfd_serving_warmstart_misses_total", 0),
            "fallbacks": m.get("dfd_serving_warmstart_fallbacks_total",
                               0),
            "canary_rejects": m.get(
                "dfd_serving_warmstart_canary_rejects_total", 0),
            "serialized": m.get("dfd_serving_warmstart_serialized_total",
                                0),
        }
        out.update({f"stage_{s}": v for s, v in stages.items()})
        _log(f"{label}: ready in {stages['ready']:.1f}s "
             f"(spawn {stages['spawn']:.1f} / import "
             f"{stages['import']:.1f} / params {stages['params_load']:.1f}"
             f" / compile {stages['compile']:.1f} / warm "
             f"{stages['warm']:.1f}); backend compiles "
             f"{out['compiles']:.0f}, store "
             f"hits/misses/fallbacks/canary-rejects = "
             f"{out['hits']:.0f}/{out['misses']:.0f}/"
             f"{out['fallbacks']:.0f}/{out['canary_rejects']:.0f}")
        return out
    finally:
        _terminate_proc(proc)


def _poll_autoscaler_json(netloc: str) -> Dict:
    host, port = netloc.split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=5)
    try:
        conn.request("GET", "/autoscaler")
        resp = conn.getresponse()
        import json as _json
        return _json.loads(resp.read().decode("utf-8"))
    finally:
        conn.close()


def _run_standby_promotion(args, ckpt: str, store: str
                           ) -> Dict[str, float]:
    """Router owning 1 replica + 1 parked standby (both over the warm
    store): a closed-loop spike must turn into serving capacity via
    registry PROMOTION — no spawn, no compile — inside the standby bar."""
    replica_args = (f"--model {args.model} --image-size "
                    f"{args.image_size} --img-num {args.img_num} "
                    f"--buckets {args.buckets} --wire {args.wire} "
                    f"--batch-deadline-ms 5 --max-queue 64 "
                    f"--model-path {ckpt} --warmstart-dir {store}")
    if args.single_thread_xla:
        replica_args += " --single-thread-xla"
    port = free_port()
    cmd = [sys.executable, "-m", "deepfake_detection_tpu.runners.router",
           "--port", str(port),
           "--spawn", "1", "--replica-args", replica_args,
           "--data-plane", args.data_plane,
           "--scrape-interval-s", "0.1", "--health-fail-after", "2",
           "--autoscale", "--min-replicas", "1", "--max-replicas", "2",
           "--standby-replicas", "1",
           "--autoscale-interval-s", "0.25",
           "--slo-p99-ms", "100000",
           "--autoscale-depth-high", "2", "--autoscale-depth-low", "1",
           "--autoscale-up-samples", "2",
           "--autoscale-down-samples", "9999",
           "--autoscale-up-cooldown-s", "1",
           "--autoscale-down-cooldown-s", "600"]
    env = dict(os.environ)
    if not args.keep_env:
        env.setdefault("JAX_PLATFORMS", "cpu")
    _log("spawning standby router: " + " ".join(cmd))
    proc = subprocess.Popen(cmd, cwd=_REPO, env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    netloc = f"127.0.0.1:{port}"
    stop = threading.Event()
    posters: List[_ElasticPoster] = []
    try:
        wait_fleet_ready(netloc, 1, timeout=900.0)
        # the standby must be PARKED AND FULLY WARMED before the spike —
        # that is the whole premise of the ms-scale promotion
        t0 = time.monotonic()
        while time.monotonic() - t0 < 900.0:
            try:
                st = _poll_autoscaler_json(netloc)
                if st.get("standbys", {}).get("warmed", 0) >= 1:
                    break
            except OSError:
                pass
            time.sleep(0.2)
        else:
            raise TimeoutError("standby never warmed")
        _log(f"standby parked + warmed {time.monotonic() - t0:.1f}s "
             f"after fleet-ready")
        time.sleep(1.5)                 # settle idle control ticks
        m0 = scrape_metrics(netloc)
        if m0.get("dfd_router_standby_promotions_total", 0):
            raise AssertionError("promotion before any load was offered")
        jpegs = make_jpegs(16, args.src_size)
        t_spike = time.monotonic()
        posters = [_ElasticPoster(netloc, jpegs, stop, seed=i)
                   for i in range(args.elastic_posters)]
        for p in posters:
            p.start()
        decision_s = _wait_metric(
            netloc,
            lambda m: m.get("dfd_router_standby_promotions_total", 0) >= 1,
            "standby promotion", timeout=60.0)
        wait_fleet_ready(netloc, 2, timeout=60.0)
        promote_s = time.monotonic() - t_spike
        _log(f"standby promoted {decision_s:.2f}s after the spike; "
             f"serving at {promote_s:.2f}s")
        stop.set()
        for p in posters:
            p.join(timeout=30)
        m = scrape_metrics(netloc)
        # promotion books: the scale-up rode the parked child — exactly
        # two spawns total (initial + standby park), zero at spike time
        if m.get("dfd_router_standby_promotions_total", 0) != 1:
            raise AssertionError("expected exactly one promotion")
        if m.get("dfd_router_replicas_spawned_total", 0) != 2:
            raise AssertionError(
                f"promotion must not spawn: spawned "
                f"{m.get('dfd_router_replicas_spawned_total', 0):.0f}")
        spawned = m.get("dfd_router_replicas_spawned_total", 0)
        retired = m.get("dfd_router_replicas_retired_total", 0)
        killed = m.get("dfd_router_replicas_killed_total", 0)
        alive = m.get("dfd_router_ready_replicas", 0) + \
            m.get("dfd_router_warming_replicas", 0)
        standby = m.get("dfd_router_standby_replicas", 0)
        if spawned != retired + killed + alive + standby:
            raise AssertionError(
                f"standby books do not balance: spawned {spawned:.0f} "
                f"!= retired {retired:.0f} + killed {killed:.0f} + "
                f"alive {alive:.0f} + standby {standby:.0f}")
        statuses: Dict[int, int] = {}
        for p in posters:
            for s, c in p.statuses.items():
                statuses[s] = statuses.get(s, 0) + c
        bad = {s: c for s, c in statuses.items()
               if s not in (200, 429, 503)}
        if bad:
            raise AssertionError(
                f"client-visible failures through promotion: {bad}")
        if promote_s > args.standby_bar:
            raise AssertionError(
                f"standby promotion bar missed: spike -> serving took "
                f"{promote_s:.2f}s (bar {args.standby_bar:.1f}s)")
        return {"decision_s": decision_s, "promote_s": promote_s}
    finally:
        stop.set()
        _terminate_proc(proc)


def run_coldstart_phase(args) -> List[str]:
    """ISSUE 19: the replica cold-start ladder, measured.

    Three starts of the SAME serve configuration:

    * **cold** — empty executable store: pays the full XLA compile and
      populates the store (misses == serialized, zero hits),
    * **warm store** — fresh interpreter over the populated store: every
      executable deserializes (hits == units, ZERO backend compiles —
      the jax compile-event hook is the judge, not wall clock),
    * **standby promote** — a parked fully-warmed replica turns a load
      spike into serving capacity by registry promotion (no spawn, no
      compile, books exact).

    Asserts warm >= ``--coldstart-bar``x faster than cold and promotion
    inside ``--standby-bar`` seconds."""
    workdir = tempfile.mkdtemp(prefix="bench-coldstart-")
    ckpt = os.path.join(workdir, "bench.msgpack")
    store = os.path.join(workdir, "warmstore")
    _write_bench_checkpoint(args, ckpt)

    cold = _coldstart_once(args, ckpt, store, "cold start")
    if cold["hits"] or not cold["misses"]:
        raise AssertionError(
            f"cold start books wrong: hits {cold['hits']:.0f}, misses "
            f"{cold['misses']:.0f} (store was supposed to be empty)")
    if cold["serialized"] != cold["misses"]:
        raise AssertionError(
            f"cold start must serialize every miss: "
            f"{cold['serialized']:.0f} != {cold['misses']:.0f}")

    warm = _coldstart_once(args, ckpt, store, "warm-store start")
    if warm["compiles"] != 0:
        raise AssertionError(
            f"warm path paid {warm['compiles']:.0f} backend compile(s) "
            f"— the zero-compile contract is broken")
    if warm["misses"] or warm["fallbacks"] or warm["canary_rejects"]:
        raise AssertionError(
            f"warm start books wrong: misses {warm['misses']:.0f}, "
            f"fallbacks {warm['fallbacks']:.0f}, canary rejects "
            f"{warm['canary_rejects']:.0f}")
    if warm["hits"] != cold["misses"]:
        raise AssertionError(
            f"warm start must hit every unit: {warm['hits']:.0f} != "
            f"{cold['misses']:.0f}")
    speedup = cold["ready_s"] / max(warm["ready_s"], 1e-9)
    if speedup < args.coldstart_bar:
        raise AssertionError(
            f"cold-start bar missed: warm is only {speedup:.2f}x faster "
            f"than cold (bar {args.coldstart_bar:.1f}x)")
    _log(f"warm store start is {speedup:.1f}x faster than cold")

    standby = _run_standby_promotion(args, ckpt, store)

    def row(label, r):
        return (f"| {label} | {r['ready_s']:.1f}s | "
                f"{r['stage_spawn']:.1f}s | {r['stage_import']:.1f}s | "
                f"{r['stage_params_load']:.1f}s | "
                f"{r['stage_compile']:.1f}s | {r['stage_warm']:.1f}s | "
                f"{r['compiles']:.0f} | {r['hits']:.0f}/"
                f"{r['misses']:.0f}/{r['fallbacks']:.0f} |")

    lines = []
    lines.append(f"**Cold start (ISSUE 19)** — `{args.model}` @ "
                 f"{args.image_size}px, buckets {args.buckets}, "
                 f"{args.wire} wire, checkpoint-backed params, on "
                 f"{os.cpu_count()} CPU core(s).  One serve "
                 f"configuration started three ways; per-stage walls "
                 f"from `dfd_serving_warmup_seconds{{stage=}}`, compile "
                 f"counts from jax's own backend-compile hook.  Exact "
                 f"store books and a scored request asserted per start; "
                 f"promotion books (no spawn at spike time) asserted in "
                 f"the standby run.")
    lines.append("")
    lines.append("| start | spawn→ready | spawn | import | params | "
                 "compile | warm | backend compiles | "
                 "hits/misses/fallbacks |")
    lines.append("|---|---|---|---|---|---|---|---|---|")
    lines.append(row("cold (empty store)", cold))
    lines.append(row("warm store", warm))
    lines.append(f"| standby promote (spike → serving) | "
                 f"{standby['promote_s']:.2f}s | — | — | — | — | — | 0 "
                 f"| promotion, no spawn |")
    lines.append("")
    lines.append(f"Warm store start is **{speedup:.1f}x** faster than "
                 f"cold (bar {args.coldstart_bar:.1f}x) with **zero** "
                 f"backend compiles; a parked standby turned the spike "
                 f"into serving capacity in "
                 f"**{standby['promote_s']:.2f}s** (decision at "
                 f"{standby['decision_s']:.2f}s, bar "
                 f"{args.standby_bar:.1f}s).")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", default="vit_tiny_patch16_224",
                    help="registered model name (default sized for a "
                         "small-CPU box)")
    ap.add_argument("--model-path", default="")
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--img-num", type=int, default=1)
    ap.add_argument("--buckets", default="1,4,16,64")
    ap.add_argument("--deadline-ms", type=float, default=4.0)
    ap.add_argument("--max-queue", type=int, default=128)
    ap.add_argument("--concurrency", default="1,4,16")
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--warmup", type=float, default=2.0)
    ap.add_argument("--src-size", type=int, default=256,
                    help="synthetic source image side before server resize")
    ap.add_argument("--retry-cap", type=float, default=2.0,
                    help="client backoff cap (s): sheds honor the "
                         "server's Retry-After with capped exponential "
                         "backoff up to this")
    ap.add_argument("--single-thread-xla", action="store_true",
                    help="serve with XLA capped to one CPU thread (pays "
                         "off for small models: decode gets the cores)")
    ap.add_argument("--wire", default="uint8",
                    choices=["uint8", "float32"],
                    help="host->device wire format (uint8 = device-side "
                         "normalize, the high-throughput mode; float32 = "
                         "bit-exact CLI parity, the server default)")
    ap.add_argument("--url", default="",
                    help="target an already-running server instead of "
                         "spawning one")
    ap.add_argument("--no-baseline", action="store_true")
    ap.add_argument("--no-cold-baseline", action="store_true")
    ap.add_argument("--no-engine-loop", action="store_true")
    ap.add_argument("--keep-env", action="store_true",
                    help="inherit the env as-is (e.g. to bench on TPU)")
    ap.add_argument("--dtype", default="",
                    help="serving PTQ dtype of the primary model "
                         "(f32|bf16|int8; quant_parity.py owns the "
                         "accuracy gate)")
    ap.add_argument("--models", default="",
                    help="extra model-table specs passed through to the "
                         "server (ServeConfig --models grammar)")
    ap.add_argument("--cascade", default="",
                    help="run the two-tier cascade matrix: this --models "
                         "id triages student-first in a SECOND server "
                         "phase, compared against the flagship-only "
                         "phase at the same concurrency")
    ap.add_argument("--replicas", default="",
                    help="fleet matrix (ISSUE 15): comma list of fleet "
                         "sizes (e.g. 1,2,4) — each size spawns that "
                         "many serve replicas behind runners/router.py "
                         "and drives the SAME closed loop through the "
                         "router at the max --concurrency, compared "
                         "against the single-process row")
    ap.add_argument("--data-plane", default="evloop",
                    choices=["evloop", "threads"],
                    help="router data plane for the fleet phases "
                         "(ISSUE 16: evloop is the event-loop hot "
                         "path, threads the original fallback)")
    ap.add_argument("--relay-ceiling", action="store_true",
                    help="run ONLY the relay-ceiling phase (ISSUE 16): "
                         "both data planes against instant stub "
                         "upstreams — no model, no replicas; asserts "
                         "exact books, byte-identical re-export and "
                         "the evloop>=bar×threads rate")
    ap.add_argument("--relay-duration", type=float, default=8.0,
                    help="measured seconds per plane in the "
                         "relay-ceiling phase")
    ap.add_argument("--relay-concurrency", type=int, default=8,
                    help="keep-alive clients per plane in the "
                         "relay-ceiling phase")
    ap.add_argument("--relay-bar", type=float, default=-1.0,
                    help="minimum evloop/threads relay-rate ratio; "
                         "<=0 means auto (1.05 = plane-ordering "
                         "tripwire; --relay-bar 5 re-arms the "
                         "pre-registered bar for an off-core harness)")
    ap.add_argument("--smoke", action="store_true",
                    help="short CI-gate variant of --relay-ceiling: "
                         "3s per plane (concurrency stays >=8 — below "
                         "the epoll batching regime the comparison "
                         "measures latency, not relay cost)")
    ap.add_argument("--zipf", type=float, default=0.0,
                    help="run ONLY the verdict-cache phase (ISSUE 17): "
                         "closed-loop Zipf(s) popularity over "
                         "--zipf-clips distinct clips, cache-off vs "
                         "cache-on at the max --concurrency, exact "
                         "books + zero-recompile asserts (e.g. "
                         "--zipf 1.1)")
    ap.add_argument("--zipf-clips", type=int, default=256,
                    help="distinct synthetic clips in the zipf phase "
                         "(must exceed the cache capacity)")
    ap.add_argument("--zipf-cache-entries", type=int, default=64,
                    help="verdict-cache capacity for the cache-on zipf "
                         "phase (deliberately < --zipf-clips)")
    ap.add_argument("--zipf-bar", type=float, default=-1.0,
                    help="minimum cache-on/cache-off effective req/s "
                         "ratio; <=0 = auto ordering tripwire (1.05; "
                         "the pre-registered heavy-flagship bar at "
                         "s=1.1 is 3.0)")
    ap.add_argument("--elastic", action="store_true",
                    help="run ONLY the elastic autoscale phase "
                         "(ISSUE 18): 1 cold replica behind the "
                         "autoscaling router, a closed-loop spike, "
                         "measured spike->decision, spike->capacity "
                         "and load-off->retirement times, exact books "
                         "+ bit-exact decision-trace replay")
    ap.add_argument("--elastic-posters", type=int, default=8,
                    help="closed-loop posters in the elastic spike "
                         "(must drive per-replica depth past the "
                         "breach line of 2)")
    ap.add_argument("--elastic-hold", type=float, default=4.0,
                    help="seconds the spike keeps running after the "
                         "second replica is serving")
    ap.add_argument("--coldstart", action="store_true",
                    help="run ONLY the cold-start phase (ISSUE 19): "
                         "cold vs warm-store vs standby-promote starts "
                         "of one serve configuration, per-stage "
                         "breakdown, exact store/promotion books, "
                         "zero-backend-compile + canary asserts")
    ap.add_argument("--coldstart-bar", type=float, default=2.5,
                    help="minimum cold/warm spawn->ready ratio (the "
                         "pre-registered ISSUE 19 bar is 2.5)")
    ap.add_argument("--standby-bar", type=float, default=2.0,
                    help="maximum spike->serving seconds for a standby "
                         "promotion (the pre-registered bar is 2 s)")
    ap.add_argument("--traffic-mix", type=float, default=0.8,
                    help="fraction of bench traffic the calibrated "
                         "suspect band lets the student clear (the rest "
                         "escalates to the flagship)")
    ap.add_argument("--out", default="", help="write the markdown here")
    args = ap.parse_args(argv)
    if args.cascade and not args.models:
        ap.error("--cascade needs --models naming the student spec")
    if args.cascade and not 0.0 < args.traffic_mix < 1.0:
        ap.error("--traffic-mix must be in (0, 1)")

    if args.relay_ceiling:
        if args.smoke:
            args.relay_duration = min(args.relay_duration, 3.0)
        table = "\n".join(run_relay_ceiling(args))
        print(table)
        if args.out:
            with open(args.out, "w") as f:
                f.write(table + "\n")
            _log(f"wrote {args.out}")
        return 0

    if args.coldstart:
        table = "\n".join(run_coldstart_phase(args))
        print(table)
        if args.out:
            with open(args.out, "w") as f:
                f.write(table + "\n")
            _log(f"wrote {args.out}")
        return 0

    if args.elastic:
        if args.smoke:
            args.elastic_hold = min(args.elastic_hold, 2.0)
        table = "\n".join(run_elastic_phase(args))
        print(table)
        if args.out:
            with open(args.out, "w") as f:
                f.write(table + "\n")
            _log(f"wrote {args.out}")
        return 0

    if args.zipf > 0:
        if args.smoke:
            args.duration = min(args.duration, 4.0)
            args.warmup = min(args.warmup, 1.0)
        table = "\n".join(run_zipf_phase(args))
        print(table)
        if args.out:
            with open(args.out, "w") as f:
                f.write(table + "\n")
            _log(f"wrote {args.out}")
        return 0

    jpegs = make_jpegs(32, args.src_size)
    _log(f"{len(jpegs)} synthetic JPEGs, ~{len(jpegs[0]) // 1024} KiB each")

    proc = None
    if args.url:
        netloc = args.url.replace("http://", "").rstrip("/")
    else:
        proc, netloc = spawn_server(args)
    try:
        wait_ready(netloc)
        m0 = scrape_metrics(netloc)
        compiles_at_ready = m0.get("dfd_serving_compiles_total", 0)
        # the REAL probe: backend compiles observed by jax's monitoring
        # hook inside the server process (the engine counter above only
        # counts its own AOT builds and can't see a stray jit)
        backend_at_ready = m0.get("dfd_serving_backend_compiles_total", 0)

        rows = []
        for c in [int(x) for x in args.concurrency.split(",") if x]:
            _log(f"closed loop: concurrency {c}, {args.duration:.0f}s "
                 f"(+{args.warmup:.0f}s warmup)")
            r = run_load(netloc, jpegs, c, args.duration, args.warmup,
                         retry_cap_s=args.retry_cap)
            _log(f"  -> {r['rps']:.1f} req/s, p50 {r['p50']:.1f} ms, "
                 f"p95 {r['p95']:.1f} ms, statuses {r['statuses']}")
            rows.append((c, r))

        m1 = scrape_metrics(netloc)
        compiles_after = m1.get("dfd_serving_compiles_total", 0)
        backend_after = m1.get("dfd_serving_backend_compiles_total", 0)
        recompiles = (compiles_after - compiles_at_ready) + \
                     (backend_after - backend_at_ready)
        batches = m1.get("dfd_serving_batches_total", 0)
        real_rows = m1.get("dfd_serving_batch_rows_total", 0)
        padded = m1.get("dfd_serving_padded_rows_total", 0)
        labeled_main = scrape_metrics_labeled(netloc)
    finally:
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

    eng = None
    if not args.no_engine_loop:
        c = max(int(x) for x in args.concurrency.split(","))
        _log(f"engine closed loop (no socket layer), concurrency {c} ...")
        eng = engine_closed_loop(args, jpegs, c, args.duration, args.warmup)
        _log(f"  -> {eng['rps']:.1f} req/s, p50 {eng['p50']:.1f} ms")

    cas = cas_labeled = None
    if args.cascade:
        c = max(int(x) for x in args.concurrency.split(","))
        cas, cas_labeled = run_cascade_phase(args, jpegs, c)

    fleet_rows = []
    if args.replicas:
        c = max(int(x) for x in args.concurrency.split(","))
        for n in [int(x) for x in args.replicas.split(",") if x]:
            fleet_rows.append(run_fleet_phase(args, jpegs, n, c))

    seq = None
    if not args.no_baseline:
        _log("warm sequential baseline (runners/test.py loop) ...")
        seq = warm_sequential_baseline(args, jpegs)
        _log(f"  -> {seq:.1f} img/s")
    cold = None
    if not args.no_cold_baseline:
        _log("cold one-shot baseline (fresh interpreter) ...")
        cold = cold_oneshot_baseline(args, jpegs[0])
        if cold:
            _log(f"  -> {cold:.1f} s/image")

    # ------------------------------------------------------------------
    lines = []
    lines.append(f"Config: `{args.model}` @ {args.image_size}² × "
                 f"{3 * args.img_num}ch, buckets `{args.buckets}`, "
                 f"deadline {args.deadline_ms} ms, "
                 f"{os.cpu_count()} CPU cores, platform "
                 f"`{os.environ.get('JAX_PLATFORMS', 'default')}`")
    lines.append("")
    lines.append("| setup | throughput (img/s) | vs warm CLI loop | "
                 "p50 (ms) | p95 (ms) | p99 (ms) |")
    lines.append("|---|---|---|---|---|---|")
    if cold:
        rate = 1.0 / cold
        ratio = f"{rate / seq:.2f}×" if seq else "–"
        lines.append(f"| one-shot CLI, cold (status quo) | {rate:.2f} | "
                     f"{ratio} | {cold * 1000:.0f} | – | – |")
    if seq:
        lines.append(f"| warm sequential CLI loop (baseline) | {seq:.1f} | "
                     f"1.00× | – | – | – |")
    for c, r in rows:
        ratio = f"{r['rps'] / seq:.2f}×" if seq else "–"
        shed = r["statuses"].get(429, 0)
        note = f" ({shed} shed)" if shed else ""
        lines.append(f"| server (HTTP), concurrency {c}{note} | "
                     f"{r['rps']:.1f} | {ratio} | {r['p50']:.1f} | "
                     f"{r['p95']:.1f} | {r['p99']:.1f} |")
    if eng:
        c = max(int(x) for x in args.concurrency.split(","))
        ratio = f"{eng['rps'] / seq:.2f}×" if seq else "–"
        lines.append(f"| batcher+engine, no socket layer, concurrency {c} "
                     f"| {eng['rps']:.1f} | {ratio} | {eng['p50']:.1f} | "
                     f"{eng['p95']:.1f} | {eng['p99']:.1f} |")
    if cas is not None:
        c = max(int(x) for x in args.concurrency.split(","))
        flag_row = next((r for cc, r in rows if cc == c), None)
        ratio = (f"{cas['rps'] / flag_row['rps']:.2f}×"
                 if flag_row and flag_row["rps"] else "–")
        books = cas["cascade"]
        vs_seq = f"{cas['rps'] / seq:.2f}×" if seq else "–"
        lines.append(
            f"| cascade ({args.cascade} triages, band "
            f"[{cas['band'][0]:.3f}, {cas['band'][1]:.3f}]), "
            f"concurrency {c} | {cas['rps']:.1f} | {vs_seq} | "
            f"{cas['p50']:.1f} | {cas['p95']:.1f} | {cas['p99']:.1f} |")
        lines.append("")
        lines.append(
            f"**Cascade vs flagship-only at concurrency {c}: {ratio} "
            f"effective req/s** ({books.get('triaged', 0):.0f} triaged = "
            f"{books.get('cleared', 0):.0f} cleared + "
            f"{books.get('escalated', 0):.0f} escalated; "
            f"{books.get('escalated', 0):.0f} escalated = "
            f"{books.get('flagship_scored', 0):.0f} flagship-scored + "
            f"{books.get('escalation_failed', 0):.0f} failed — books "
            f"exact, zero recompiles).")
    if fleet_rows:
        c = max(int(x) for x in args.concurrency.split(","))
        flag_row = next((r for cc, r in rows if cc == c), None)
        base_rps = flag_row["rps"] if flag_row else None
        lines.append("")
        lines.append(f"**Fleet matrix (ISSUE 15)** — N serve replicas "
                     f"behind `runners/router.py`, same closed loop at "
                     f"concurrency {c}; scaling is vs the single-process "
                     f"HTTP row above (the measured per-process host "
                     f"ceiling).  Router books exact and zero replica "
                     f"recompiles asserted every phase.")
        lines.append("")
        lines.append("| replicas | throughput (req/s) | vs 1 process | "
                     "p50 (ms) | p95 (ms) | router books "
                     "(routed=fwd+mig+shed+fail) | per-replica spread |")
        lines.append("|---|---|---|---|---|---|---|")
        for r in fleet_rows:
            ratio = (f"{r['rps'] / base_rps:.2f}×" if base_rps else "–")
            b = r["books"]
            books = (f"{b.get('routed', 0):.0f}="
                     f"{b.get('forwarded', 0):.0f}+"
                     f"{b.get('migrated', 0):.0f}+"
                     f"{b.get('shed', 0):.0f}+{b.get('failed', 0):.0f}")
            spread = "/".join(f"{v:.0f}"
                              for _, v in sorted(r["spread"].items()))
            lines.append(f"| {r['replicas']} (router in front) | "
                         f"{r['rps']:.1f} | {ratio} | {r['p50']:.1f} | "
                         f"{r['p95']:.1f} | {books} | {spread} |")
    lines.append("")
    lines.append(f"Compile probe: {compiles_at_ready:.0f} bucket "
                 f"executables at ready, **{recompiles:+.0f} after "
                 f"{sum(r['statuses'].get(200, 0) for _, r in rows)} "
                 f"scored requests** (zero = the compile cache held); "
                 f"{batches:.0f} device batches, {real_rows:.0f} real + "
                 f"{padded:.0f} padded rows "
                 f"({100 * padded / max(1, real_rows + padded):.1f}% "
                 f"padding).")
    for title, labeled in (("flagship-only phase", labeled_main),
                           ("cascade phase", cas_labeled)):
        if not labeled:
            continue
        bucket_rows_md = per_bucket_padding_rows(labeled)
        model_rows_md = per_model_rows(labeled)
        if model_rows_md:
            lines.append("")
            lines.append(f"Per-model request books ({title}):")
            lines.append("")
            lines.extend(model_rows_md)
        if bucket_rows_md:
            lines.append("")
            lines.append(f"Per-bucket padding ({title}):")
            lines.append("")
            lines.extend(bucket_rows_md)
    table = "\n".join(lines)
    print(table)

    if args.out:
        with open(args.out, "w") as f:
            f.write("# SERVE_BENCH — dynamic-batching server vs one-shot "
                    "CLI\n\n")
            f.write("Generated by `tools/bench_serve.py` (closed-loop "
                    "load generator, persistent\nkeep-alive connections; "
                    "baselines described in the tool's docstring).\n\n")
            f.write(table + "\n")
        _log(f"wrote {args.out}")

    if recompiles != 0:
        _log(f"FAIL: {recompiles:+.0f} recompiles after warmup")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
