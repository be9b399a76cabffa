"""Closed-loop synthetic-stream load generator for the streaming
subsystem (ISSUE 8).

Spawns ``runners/stream.py`` as a subprocess (or targets ``--url``),
opens N concurrent stream sessions, and pushes synthetic MJPEG chunks
(multipart/x-mixed-replace, JPEG parts) through the full pipeline —
decode → full-frame track → temporal windows → the serving engine's
AOT-warmed buckets — reporting a throughput/latency table plus three
acceptance probes:

* **zero recompiles**: ``dfd_serving_backend_compiles_total`` (jax's own
  backend-compile monitoring hook inside the server) must not grow
  across the load phases — the serving engine's guarantee, now under a
  streaming traffic mix;
* **verdict transitions**: a planted real→fake score flip
  (``--verdict-vector``, consumed by the verdict machines while windows
  still ride the real engine) must produce exactly the
  real→suspect→fake transition windows the EMA/hysteresis math predicts
  — the bench recomputes the expectation with the SAME VerdictMachine
  class and compares events;
* **counted backpressure**: a flood phase (windows emitted faster than
  the engine drains, tiny per-stream bound) must account for every
  window: scored + dropped + shed + failed + pending == emitted — drops
  are counted, never silent.

Defaults are sized for a small-CPU box (the pipeline is
chip-independent); on real accelerators pass the flagship config.

    JAX_PLATFORMS=cpu \
        python tools/bench_stream.py --out STREAM_BENCH.md
"""

from __future__ import annotations

import argparse
import http.client
import io
import json
import os
import socket
import statistics
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _log(msg: str) -> None:
    print(f"[bench_stream] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# synthetic MJPEG material
# ---------------------------------------------------------------------------

def make_stream_jpegs(n: int, w: int, h: int, seed: int = 0) -> List[bytes]:
    """Photographic-ish synthetic frames (bench_serve's recipe: smooth
    gradients + noise; pure noise compresses/decodes unrealistically)."""
    from PIL import Image
    out = []
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
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


def mjpeg_chunk(jpegs: List[bytes]) -> bytes:
    return b"".join(
        b"--frame\r\nContent-Type: image/jpeg\r\n\r\n" + j + b"\r\n"
        for j in jpegs) + b"--frame--\r\n"


_MJPEG_CTYPE = "multipart/x-mixed-replace; boundary=frame"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# server lifecycle (bench_serve idiom)
# ---------------------------------------------------------------------------

def spawn_server(args) -> Tuple[subprocess.Popen, str]:
    port = free_port()
    cmd = [sys.executable, "-m", "deepfake_detection_tpu.runners.stream",
           "--model", args.model, "--image-size", str(args.image_size),
           "--img-num", str(args.img_num), "--port", str(port),
           "--buckets", args.buckets,
           "--batch-deadline-ms", str(args.deadline_ms),
           "--max-inflight-windows", str(args.max_inflight),
           "--wire", args.wire]
    if args.single_thread_xla:
        cmd += ["--single-thread-xla"]
    if args.window_hop:
        cmd += ["--window-hop", str(args.window_hop)]
    if args.verdict_vector:
        cmd += ["--verdict-vector", args.verdict_vector]
    if args.model_path:
        cmd += ["--model-path", args.model_path]
    env = dict(os.environ)
    if not args.keep_env:
        env.setdefault("JAX_PLATFORMS", "cpu")
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
# stream client
# ---------------------------------------------------------------------------

class StreamClient(threading.Thread):
    """One closed-loop stream: open session, push MJPEG chunks on a
    keep-alive connection until stopped, close session."""

    def __init__(self, netloc: str, stream_id: str, chunk: bytes,
                 frames_per_chunk: int, stop: threading.Event):
        super().__init__(daemon=True)
        self.netloc = netloc
        self.stream_id = stream_id
        self.chunk = chunk
        self.frames_per_chunk = frames_per_chunk
        self.stop_evt = stop
        self.ack_lat_ms: List[float] = []
        self.chunks = 0
        self.frames = 0
        self.final_status: Optional[dict] = None
        self.error: Optional[str] = None

    def _conn(self) -> http.client.HTTPConnection:
        host, port = self.netloc.split(":")
        return http.client.HTTPConnection(host, int(port), timeout=30)

    def _req(self, conn, method, path, body=None, ctype=None) -> dict:
        headers = {"Content-Type": ctype} if ctype else {}
        conn.request(method, path, body=body, headers=headers)
        r = conn.getresponse()
        raw = r.read()
        if r.status >= 400:
            raise RuntimeError(f"{method} {path} -> {r.status}: "
                               f"{raw[:200]!r}")
        return json.loads(raw) if raw[:1] == b"{" else {}

    def run(self) -> None:
        try:
            conn = self._conn()
            self._req(conn, "POST", "/streams",
                      json.dumps({"stream_id": self.stream_id}).encode(),
                      "application/json")
            while not self.stop_evt.is_set():
                t0 = time.monotonic()
                self._req(conn, "POST",
                          f"/streams/{self.stream_id}/frames",
                          self.chunk, _MJPEG_CTYPE)
                self.ack_lat_ms.append(
                    (time.monotonic() - t0) * 1000.0)
                self.chunks += 1
                self.frames += self.frames_per_chunk
            self.final_status = self._req(
                conn, "GET", f"/streams/{self.stream_id}")
            self._req(conn, "DELETE", f"/streams/{self.stream_id}")
            conn.close()
        except Exception as e:                         # noqa: BLE001
            self.error = repr(e)


def run_load(netloc: str, streams: int, duration: float, jpegs: List[bytes],
             frames_per_chunk: int) -> dict:
    stop = threading.Event()
    clients = []
    for i in range(streams):
        chunk = mjpeg_chunk([jpegs[(i + k) % len(jpegs)]
                             for k in range(frames_per_chunk)])
        clients.append(StreamClient(netloc, f"bench-{i}", chunk,
                                    frames_per_chunk, stop))
    t0 = time.monotonic()
    for c in clients:
        c.start()
    time.sleep(duration)
    stop.set()
    for c in clients:
        c.join(timeout=60)
    dt = time.monotonic() - t0
    errors = [c.error for c in clients if c.error]
    if errors:
        raise RuntimeError(f"client errors: {errors}")
    lats = sorted(x for c in clients for x in c.ack_lat_ms)

    def pct(p):
        return lats[min(len(lats) - 1, int(p * len(lats)))] if lats \
            else float("nan")

    frames = sum(c.frames for c in clients)
    return {
        "streams": streams,
        "duration_s": dt,
        "chunks": sum(c.chunks for c in clients),
        "frames": frames,
        "fps": frames / dt,
        "ack_p50_ms": pct(0.50),
        "ack_p95_ms": pct(0.95),
        "ack_mean_ms": statistics.fmean(lats) if lats else float("nan"),
        "statuses": [c.final_status for c in clients],
    }


# ---------------------------------------------------------------------------
# acceptance probes
# ---------------------------------------------------------------------------

def expected_transitions(vector_spec: str, ema_alpha: float,
                         thresholds) -> List[Tuple[str, str, int]]:
    """Replay the planted vector through the SAME VerdictMachine class
    the server uses → the exact (from, to, window) transition list."""
    from deepfake_detection_tpu.streaming.ingest import parse_verdict_vector
    from deepfake_detection_tpu.streaming.verdict import VerdictMachine
    vm = VerdictMachine(thresholds, ema_alpha=ema_alpha)
    out = []
    for score in parse_verdict_vector(vector_spec):
        for ev in vm.update(score):
            out.append((ev["from"], ev["to"], ev["windows"]))
    return out


def run_verdict_probe(netloc: str, args) -> dict:
    """One stream pushing exactly enough frames to consume the planted
    vector; compares emitted transition events against the machine's own
    replay."""
    from deepfake_detection_tpu.streaming.ingest import parse_verdict_vector
    from deepfake_detection_tpu.streaming.verdict import VerdictThresholds
    vector = parse_verdict_vector(args.verdict_vector)
    n_windows = len(vector)
    hop = args.window_hop or args.img_num
    n_frames = args.img_num + (n_windows - 1) * hop
    jpegs = make_stream_jpegs(min(n_frames, 16), args.frame_w,
                              args.frame_h, seed=99)
    host, port = netloc.split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)

    def req(method, path, body=None, ctype=None):
        headers = {"Content-Type": ctype} if ctype else {}
        conn.request(method, path, body=body, headers=headers)
        r = conn.getresponse()
        raw = r.read()
        assert r.status < 400, f"{method} {path} -> {r.status}"
        return json.loads(raw)

    req("POST", "/streams",
        json.dumps({"stream_id": "verdict-probe"}).encode(),
        "application/json")
    for i in range(n_frames):
        req("POST", "/streams/verdict-probe/frames",
            mjpeg_chunk([jpegs[i % len(jpegs)]]), _MJPEG_CTYPE)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        st = req("GET", "/streams/verdict-probe")
        if st["counters"]["windows_scored"] >= n_windows:
            break
        time.sleep(0.05)
    got = [(e["from"], e["to"], e["windows"])
           for e in st["events"] if e.get("scope") == "stream"]
    req("DELETE", "/streams/verdict-probe")
    conn.close()
    want = expected_transitions(args.verdict_vector, args.verdict_ema,
                                VerdictThresholds())
    return {"want": want, "got": got, "pass": got == want,
            "final_verdict": st["verdict"],
            "windows_scored": st["counters"]["windows_scored"]}


def run_flood_probe(netloc: str, args) -> dict:
    """Concurrent unpaced raw-frame bursts (zero decode cost, so window
    production far outruns the engine): per-stream drop-oldest, batcher
    shedding and request deadlines must together ACCOUNT for every
    emitted window."""
    host, port = netloc.split(":")
    rng = np.random.default_rng(4)
    frame = np.ascontiguousarray(rng.integers(
        0, 255, (args.frame_h, args.frame_w, 3), dtype=np.uint8))
    burst = frame.tobytes() * args.flood_frames
    raw_headers = {"Content-Type": "application/x-dfd-raw",
                   "X-Frame-Width": str(args.frame_w),
                   "X-Frame-Height": str(args.frame_h)}
    errors: List[str] = []

    def flood(i: int) -> None:
        try:
            conn = http.client.HTTPConnection(host, int(port), timeout=60)
            conn.request("POST", "/streams", json.dumps(
                {"stream_id": f"flood-{i}"}).encode(),
                {"Content-Type": "application/json"})
            assert conn.getresponse().read() is not None
            for _ in range(args.flood_chunks):
                conn.request("POST", f"/streams/flood-{i}/frames", burst,
                             raw_headers)
                r = conn.getresponse()
                r.read()
                assert r.status < 400, f"flood chunk -> {r.status}"
            conn.close()
        except Exception as e:                     # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=flood, args=(i,), daemon=True)
               for i in range(args.flood_streams)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    if errors:
        raise RuntimeError(f"flood errors: {errors}")

    conn = http.client.HTTPConnection(host, int(port), timeout=60)

    def req(method, path):
        conn.request(method, path)
        r = conn.getresponse()
        raw = r.read()
        assert r.status < 400, f"{method} {path} -> {r.status}"
        return json.loads(raw)

    # let the tail drain (scored / shed / deadline-failed), then close
    # each stream — close-time drops of still-pending windows are counted
    # into windows_dropped by the manager, so after DELETE the books must
    # balance exactly
    totals = {k: 0 for k in ("emitted", "scored", "dropped", "shed",
                             "failed")}
    balanced = True
    for i in range(args.flood_streams):
        sid = f"flood-{i}"
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            c = req("GET", f"/streams/{sid}")["counters"]
            accounted = (c["windows_scored"] + c["windows_dropped"] +
                         c["windows_shed"] + c["windows_failed"])
            if accounted >= c["windows_emitted"]:
                break
            time.sleep(0.1)
        c = req("DELETE", f"/streams/{sid}")["counters"]
        accounted = (c["windows_scored"] + c["windows_dropped"] +
                     c["windows_shed"] + c["windows_failed"])
        balanced = balanced and accounted == c["windows_emitted"]
        totals["emitted"] += c["windows_emitted"]
        totals["scored"] += c["windows_scored"]
        totals["dropped"] += c["windows_dropped"]
        totals["shed"] += c["windows_shed"]
        totals["failed"] += c["windows_failed"]
    conn.close()
    totals["balanced"] = balanced
    totals["backpressured"] = (totals["dropped"] + totals["shed"] +
                               totals["failed"]) > 0
    return totals


# ---------------------------------------------------------------------------
# host-ceiling mode (ISSUE 20): engine nulled on both sides, host path
# measured — the bench_backfill null-device idiom for streaming
# ---------------------------------------------------------------------------

class _NullRequest:
    __slots__ = ("_scores", "from_cache")

    def __init__(self, scores):
        self._scores = scores
        self.from_cache = False

    def result(self, timeout=None):
        return self._scores


class _NullBatcher:
    """Null engine: ``submit`` performs the engine's ``_pad_batch`` slab
    write (a fresh zeroed row + the payload's gather — the exact host
    copy a real engine performs) and resolves instantly with a fixed
    score row.  Everything else about the host path — decode, track,
    canvas, digest, window assembly, dispatch, verdict fold — is real.
    With ``cache`` attached it mirrors the micro-batcher's exact-key
    probe so the session's content keys resolve as counted hits."""

    def __init__(self, cache=None):
        self.cache = cache
        self._scores = np.asarray([0.07, 0.93], np.float32)
        self.gathers = 0

    def submit(self, array, timeout_s=None, model_id=None,
               content_key=None):
        req = _NullRequest(self._scores)
        if self.cache is not None and content_key is not None:
            if self.cache.get(content_key[0], "null", "nullfp") is not None:
                req.from_cache = True
                return req
        buf = np.zeros(np.shape(array),
                       getattr(array, "dtype", np.uint8))
        write_into = getattr(array, "write_into", None)
        if write_into is not None:
            write_into(buf)          # FrameStack: the one gather-memcpy
        else:
            buf[...] = array         # concat payload: the slab copy
        self.gathers += 1
        if self.cache is not None and content_key is not None:
            self.cache.put(content_key[0], "null", "nullfp", self._scores)
        return req


def _proc_cpu_s() -> float:
    """Process CPU seconds (utime+stime, all threads) from
    /proc/self/stat — the PR 16 portable host-cost control."""
    with open("/proc/self/stat") as f:
        raw = f.read()
    fields = raw[raw.rindex(")") + 2:].split()
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


_HC_BOOK_TERMS = ("windows_scored", "windows_dropped", "windows_shed",
                  "windows_failed", "windows_cache_hit",
                  "windows_dup_elided")


def _host_phase(args, name: str, assembly: str, dedup: bool,
                chunks: List[List[bytes]], cache=None) -> dict:
    """One in-process phase: fresh session + dispatcher over the null
    batcher, chunks fed closed-loop for ``--duration`` seconds."""
    from deepfake_detection_tpu.config import StreamConfig
    from deepfake_detection_tpu.streaming.ingest import (StreamSession,
                                                         decode_frame_bytes)
    from deepfake_detection_tpu.streaming.metrics import StreamingMetrics
    from deepfake_detection_tpu.streaming.windows import WindowDispatcher

    cfg = StreamConfig(
        model=args.model, image_size=args.image_size,
        img_num=args.img_num, window_hop=args.window_hop or 1,
        wire=args.wire, assembly=assembly, dedup_frames=dedup)
    metrics = StreamingMetrics()
    batcher = _NullBatcher(cache=cache)
    disp = WindowDispatcher(
        batcher, max_pending=4096, request_timeout_s=10.0,
        on_result=lambda job, s, e: job.context.on_window_result(job, s, e),
        on_drop=lambda job, r: job.context.on_window_drop(job, r))
    disp.start()
    session = StreamSession(f"ceiling-{assembly}", cfg, disp, metrics,
                            args.image_size, args.wire)

    def feed(chunk: List[bytes]) -> int:
        if assembly == "concat":
            # the pre-PR handler loop: serial per-frame decode
            arrays = [a for a in (decode_frame_bytes(d) for d in chunk)
                      if a is not None]
            session.ingest_arrays(arrays)
        else:
            arrays, flags, _errs = session.decode_chunk(chunk)
            session.ingest_arrays(arrays, flags)
        return len(chunk)

    def drain(timeout: float = 30.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with session._lock:
                pending = session.windows_emitted - sum(
                    getattr(session, k) for k in _HC_BOOK_TERMS)
            if pending <= 0:
                return
            time.sleep(0.005)

    for chunk in chunks[:3]:         # warmup: imports, pools, PIL state
        feed(chunk)
    drain()
    base = {k: getattr(session, k) for k in
            _HC_BOOK_TERMS + ("windows_emitted", "frames_ingested",
                              "frames_dup_elided")}
    t0, c0 = time.monotonic(), _proc_cpu_s()
    deadline = t0 + args.duration
    frames = i = 0
    while time.monotonic() < deadline:
        frames += feed(chunks[i % len(chunks)])
        i += 1
    drain()
    t1, c1 = time.monotonic(), _proc_cpu_s()
    disp.stop()
    out = {k: getattr(session, k) - base[k] for k in base}
    with session._lock:
        balanced = session.windows_emitted == sum(
            getattr(session, k) for k in _HC_BOOK_TERMS)
    wall = t1 - t0
    emitted = out["windows_emitted"]
    out.update(
        name=name, assembly=assembly, dedup=dedup,
        cache="on" if cache is not None else "off",
        frames_fed=frames, wall_s=wall, cpu_s=c1 - c0,
        balanced=balanced, gathers=batcher.gathers,
        wps=emitted / wall if wall > 0 else 0.0,
        fps=out["frames_ingested"] / wall if wall > 0 else 0.0,
        cpu_us_per_window=(c1 - c0) * 1e6 / emitted if emitted else
        float("nan"))
    _log(f"  {name}: {out['wps']:.1f} windows/s, "
         f"{out['cpu_us_per_window']:.0f} cpu µs/window, "
         f"scored {out['windows_scored']} hit {out['windows_cache_hit']} "
         f"dup {out['windows_dup_elided']} balanced={balanced}")
    return out


def run_host_ceiling(args) -> Dict[str, dict]:
    """Three phases, engine nulled in all of them:

    * ``concat``  — the pre-PR host path (serial decode, standalone
      canvases, per-window ``np.concatenate``), unique-content frames;
    * ``ring``    — the frame-once path (batched decode, crop rings,
      FrameStack gather), same unique-content frames;
    * ``replay``  — frame-once + ``dedup_frames`` + verdict cache on a
      replayed low-motion stream (frozen runs, recurring content) — the
      regime the per-window dedup tier is built for.
    """
    from deepfake_detection_tpu.cache.store import VerdictCache
    w, h = args.frame_w, args.frame_h
    cf = args.chunk_frames
    uniq = make_stream_jpegs(48, w, h, seed=7)
    unique_chunks = [uniq[i:i + cf]
                     for i in range(0, len(uniq) - cf + 1, cf)]
    low = make_stream_jpegs(6, w, h, seed=11)
    lowmotion_chunks = [[j] * cf for j in low]

    phases: Dict[str, dict] = {}
    _log("host-ceiling phase A: concat (pre-PR path), unique frames")
    phases["concat"] = _host_phase(args, "concat (pre-PR)", "concat",
                                   False, unique_chunks)
    _log("host-ceiling phase B: ring (frame-once), unique frames")
    phases["ring"] = _host_phase(args, "ring (frame-once)", "ring",
                                 False, unique_chunks)
    _log("host-ceiling phase C: ring+dedup+cache, low-motion replay")
    phases["replay"] = _host_phase(
        args, "ring+dedup+cache (replay)", "ring", True,
        lowmotion_chunks, cache=VerdictCache(4096, 3600.0))
    return phases


def render_host_md(args, phases: Dict[str, dict]) -> str:
    import platform
    a, b, c = phases["concat"], phases["ring"], phases["replay"]
    lines = []
    w = lines.append
    w("## Host ceiling (`--host-ceiling`: engine nulled both sides)")
    w("")
    w(f"*Generated {time.strftime('%Y-%m-%d %H:%M:%S')}; host: "
      f"{os.cpu_count()} CPUs, {platform.platform()}.  In-process, no "
      f"HTTP: the null batcher still performs the engine's batch-slab "
      f"write (the gather/copy), so these rows are the host path's "
      f"ceiling, not the engine's.*")
    w("")
    w(f"Shape: img_num {args.img_num}, hop {args.window_hop or 1} "
      f"(max-overlap), wire `{args.wire}`, {args.image_size}² canvas, "
      f"{args.frame_w}×{args.frame_h} JPEG frames, "
      f"{args.chunk_frames} frames/chunk.")
    w("")
    w("| phase | windows/s | cpu µs/window | frames/s | scored | "
      "cache hit | dup elided | frames dup elided | slab gathers | "
      "books |")
    w("|---|---:|---:|---:|---:|---:|---:|---:|---:|---|")
    for p in (a, b, c):
        w(f"| {p['name']} | {p['wps']:.1f} | "
          f"{p['cpu_us_per_window']:.0f} | {p['fps']:.1f} | "
          f"{p['windows_scored']} | {p['windows_cache_hit']} | "
          f"{p['windows_dup_elided']} | {p['frames_dup_elided']} | "
          f"{p['gathers']} | "
          f"{'exact' if p['balanced'] else 'UNBALANCED'} |")
    w("")
    ru = b["wps"] / a["wps"] if a["wps"] else float("nan")
    rr = c["wps"] / a["wps"] if a["wps"] else float("nan")
    cu = a["cpu_us_per_window"] / b["cpu_us_per_window"] \
        if b["cpu_us_per_window"] else float("nan")
    cr = a["cpu_us_per_window"] / c["cpu_us_per_window"] \
        if c["cpu_us_per_window"] else float("nan")
    w(f"Ratios vs the pre-PR concat path: frame-once on unique content "
      f"**{ru:.2f}×** windows/s ({cu:.2f}× cpu/window); frame-once + "
      f"dedup + cache on the low-motion replay **{rr:.2f}×** windows/s "
      f"({cr:.2f}× cpu/window) — the pre-registered ≥3× bar targets the "
      f"replay/low-motion regime, where duplicate frames skip decode and "
      f"recurring windows resolve from the cache without a slab gather.  "
      f"Unique-content traffic pays full decode + resize on every frame "
      f"(irreducible here), so its row reports the honest copy-path gain "
      f"only.")
    w("")
    w("Zero-recompile probe: trivially satisfied in this mode (no "
      "engine); the live-engine phases above carry the real probe.")
    w("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def render_md(args, rows, verdict, flood, recompiles_delta,
              metrics_after) -> str:
    import platform
    lines = []
    w = lines.append
    w("# STREAM_BENCH — streaming-video scoring pipeline")
    w("")
    w(f"*Generated by `tools/bench_stream.py` on "
      f"{time.strftime('%Y-%m-%d %H:%M:%S')}; "
      f"host: {os.cpu_count()} CPUs, {platform.platform()}; "
      f"backend: {'as-launched' if args.keep_env else 'JAX CPU'}.*")
    w("")
    w(f"Config: model `{args.model}` @ {args.image_size}² canvas, "
      f"img_num {args.img_num} (hop "
      f"{args.window_hop or args.img_num}), wire `{args.wire}`, buckets "
      f"`{args.buckets}`, max-inflight-windows {args.max_inflight}, "
      f"frames {args.frame_w}×{args.frame_h} JPEG q88, "
      f"{args.chunk_frames} frames/chunk.")
    w("")
    w("## Closed-loop MJPEG load")
    w("")
    w("| streams | duration s | frames/s | windows scored/s | "
      "ack p50 ms | ack p95 ms | drops | sheds |")
    w("|---:|---:|---:|---:|---:|---:|---:|---:|")
    for r in rows:
        w(f"| {r['streams']} | {r['duration_s']:.1f} | {r['fps']:.1f} | "
          f"{r['wps']:.1f} | {r['ack_p50_ms']:.1f} | "
          f"{r['ack_p95_ms']:.1f} | {r['dropped']:.0f} | "
          f"{r['shed']:.0f} |")
    w("")
    w("Reading the table: the engine saturates at a fixed windows/s "
      "(device-bound); MJPEG ingest can outrun it, and the difference is "
      "shed by design — drop-oldest on the bounded per-stream queues plus "
      "batcher 429s, all counted below, while frame ingest and verdict "
      "freshness are unaffected.  Ack latency grows with stream count "
      "because acks ride the closed-loop chunk POSTs, not because "
      "scoring lags.  Size buckets/`--window-hop` to the engine's "
      "measured windows/s for a drop-free deployment.")
    w("")
    w(f"**Zero-recompile probe**: `dfd_serving_backend_compiles_total` "
      f"delta across every load/probe phase = **{recompiles_delta:.0f}** "
      f"(must be 0 — every window rode a startup-warmed bucket).")
    w("")
    w("## Verdict-transition probe (planted real→fake flip)")
    w("")
    w(f"Vector `{args.verdict_vector}`, EMA α={args.verdict_ema}: "
      f"expected transitions `{verdict['want']}`, observed "
      f"`{verdict['got']}` → "
      f"**{'PASS' if verdict['pass'] else 'FAIL'}** "
      f"(final verdict `{verdict['final_verdict']}`, "
      f"{verdict['windows_scored']} windows scored through the real "
      f"engine).")
    w("")
    w("## Backpressure accounting (flood probe)")
    w("")
    w(f"| emitted | scored | dropped (oldest) | shed (batcher) | failed "
      f"| balanced | backpressured |")
    w(f"|---:|---:|---:|---:|---:|---|---|")
    w(f"| {flood['emitted']} | {flood['scored']} | {flood['dropped']} | "
      f"{flood['shed']} | {flood['failed']} | "
      f"{'yes' if flood['balanced'] else 'NO'} | "
      f"{'yes' if flood['backpressured'] else 'NO'} |")
    w("")
    w("Every emitted window is accounted scored/dropped/shed/failed — "
      "backpressure is counted, never silent.")
    w("")
    w("## Streaming catalog after the run (excerpt)")
    w("")
    keys = ["dfd_streaming_frames_ingested_total",
            "dfd_streaming_windows_emitted_total",
            "dfd_streaming_windows_scored_total",
            "dfd_streaming_windows_dropped_total",
            "dfd_streaming_windows_shed_total",
            "dfd_streaming_streams_opened_total",
            "dfd_serving_batches_total",
            "dfd_serving_batch_rows_total"]
    w("```")
    for k in keys:
        if k in metrics_after:
            w(f"{k} {metrics_after[k]:.0f}")
    w("```")
    w("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", default="mobilenetv3_small_100",
                    help="registered model name (default sized for a "
                         "small-CPU box)")
    ap.add_argument("--model-path", default="")
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--img-num", type=int, default=4)
    ap.add_argument("--buckets", default="1,4,8")
    ap.add_argument("--deadline-ms", type=float, default=4.0)
    ap.add_argument("--wire", default="float32",
                    choices=["float32", "uint8"])
    ap.add_argument("--window-hop", type=int, default=0)
    ap.add_argument("--max-inflight", type=int, default=4)
    ap.add_argument("--streams", default="1,4",
                    help="comma list of concurrent-stream counts")
    ap.add_argument("--duration", type=float, default=15.0)
    ap.add_argument("--chunk-frames", type=int, default=8)
    ap.add_argument("--frame-w", type=int, default=96)
    ap.add_argument("--frame-h", type=int, default=80)
    ap.add_argument("--verdict-vector", default="0.05*4,0.95*8")
    ap.add_argument("--verdict-ema", type=float, default=0.3,
                    help="must match the server's --verdict-ema-alpha")
    ap.add_argument("--flood-frames", type=int, default=256,
                    help="raw frames per flood chunk (zero-decode wire)")
    ap.add_argument("--flood-chunks", type=int, default=4)
    ap.add_argument("--flood-streams", type=int, default=6)
    ap.add_argument("--single-thread-xla", action="store_true",
                    help="serve with XLA capped to one CPU thread "
                         "(bench_serve's small-model tuning; also what "
                         "lets the flood probe actually outrun the "
                         "engine on a many-core box)")
    ap.add_argument("--url", default="",
                    help="target an already-running server (must have "
                         "been launched with the same --verdict-vector)")
    ap.add_argument("--keep-env", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run (CI smoke)")
    ap.add_argument("--host-ceiling", action="store_true",
                    help="in-process host-path bench: engine nulled on "
                         "both sides (concat vs ring vs ring+dedup+"
                         "cache), windows/s + cpu µs/window from "
                         "/proc/self/stat")
    ap.add_argument("--out", default="", help="write the markdown here")
    args = ap.parse_args(argv)
    if args.smoke:
        args.duration = min(args.duration, 3.0)
        args.streams = "2"
        args.flood_chunks = 1
        args.flood_frames = 128
        args.flood_streams = 3

    if args.host_ceiling:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        phases = run_host_ceiling(args)
        md = render_host_md(args, phases)
        print(md)
        if args.out:
            with open(args.out, "w") as f:
                f.write(md)
            _log(f"wrote {args.out}")
        ratio = phases["replay"]["wps"] / phases["concat"]["wps"] \
            if phases["concat"]["wps"] else 0.0
        ok = all(p["balanced"] for p in phases.values()) and ratio >= 3.0
        if not ok:
            _log("HOST-CEILING ACCEPTANCE FAILURE "
                 f"(ratio {ratio:.2f}, books "
                 f"{[p['balanced'] for p in phases.values()]})")
        return 0 if ok else 1

    jpegs = make_stream_jpegs(16, args.frame_w, args.frame_h)
    _log(f"{len(jpegs)} synthetic JPEGs, ~{len(jpegs[0]) // 1024} KiB "
         f"each")

    proc = None
    if args.url:
        netloc = args.url.replace("http://", "").rstrip("/")
    else:
        proc, netloc = spawn_server(args)
    try:
        wait_ready(netloc)
        m0 = scrape_metrics(netloc)
        backend0 = m0.get("dfd_serving_backend_compiles_total", 0)

        rows = []
        for n in [int(x) for x in args.streams.split(",") if x]:
            before = scrape_metrics(netloc)
            _log(f"load: {n} streams × {args.duration:.0f}s")
            r = run_load(netloc, n, args.duration, jpegs,
                         args.chunk_frames)
            after = scrape_metrics(netloc)
            r["wps"] = (after["dfd_streaming_windows_scored_total"] -
                        before["dfd_streaming_windows_scored_total"]) / \
                r["duration_s"]
            r["dropped"] = \
                after["dfd_streaming_windows_dropped_total"] - \
                before["dfd_streaming_windows_dropped_total"]
            r["shed"] = after["dfd_streaming_windows_shed_total"] - \
                before["dfd_streaming_windows_shed_total"]
            _log(f"  -> {r['fps']:.1f} frames/s, {r['wps']:.1f} "
                 f"windows/s, ack p50 {r['ack_p50_ms']:.1f} ms, "
                 f"drops {r['dropped']:.0f} sheds {r['shed']:.0f}")
            rows.append(r)

        _log("verdict probe (planted real→fake flip)")
        verdict = run_verdict_probe(netloc, args)
        _log(f"  -> {'PASS' if verdict['pass'] else 'FAIL'}: "
             f"{verdict['got']}")

        _log("flood probe (backpressure accounting)")
        flood = run_flood_probe(netloc, args)
        _log(f"  -> emitted {flood['emitted']}, scored {flood['scored']}, "
             f"dropped {flood['dropped']}, shed {flood['shed']}, "
             f"balanced={flood['balanced']}")

        m1 = scrape_metrics(netloc)
        recompiles_delta = \
            m1.get("dfd_serving_backend_compiles_total", 0) - backend0
        md = render_md(args, rows, verdict, flood, recompiles_delta, m1)
        print(md)
        if args.out:
            with open(args.out, "w") as f:
                f.write(md)
            _log(f"wrote {args.out}")
        ok = verdict["pass"] and flood["balanced"] and \
            recompiles_delta == 0
        if not ok:
            _log("ACCEPTANCE FAILURE (see report)")
        return 0 if ok else 1
    finally:
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()


if __name__ == "__main__":
    sys.exit(main())
