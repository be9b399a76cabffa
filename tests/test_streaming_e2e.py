"""Streaming pipeline e2e (ISSUE 8): localhost session round trips
against a real warmed engine, window-score ↔ CLI bit-identity, planted
verdict transitions, and a fresh-interpreter runner drive.

Fast tier (``streaming`` marker): small conv model at a 32² canvas with
``img_num=2`` clips, so the four bucket programs stay cheap and hit the
persistent compilation cache; the subprocess test reuses the same model/
canvas so its warmup is cache-warm too (the chaos-tier idiom).
"""

import dataclasses
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfake_detection_tpu.config import StreamConfig
from deepfake_detection_tpu.models import create_model, init_model
from deepfake_detection_tpu.params import make_score_fn, normalize_concat
from deepfake_detection_tpu.serving.batcher import MicroBatcher
from deepfake_detection_tpu.serving.engine import InferenceEngine
from deepfake_detection_tpu.serving.metrics import ServingMetrics
from deepfake_detection_tpu.streaming.ingest import (StreamManager,
                                                     make_stream_server)
from deepfake_detection_tpu.streaming.metrics import StreamingMetrics
from deepfake_detection_tpu.streaming.windows import WindowDispatcher

pytestmark = pytest.mark.streaming

_MODEL = "mobilenetv3_small_100"
_SIZE = 32
_NUM = 2


def _perturbed_variables(model, size, chans, seed=0):
    """test_serving's helper: nudge every param so scores discriminate
    (zoo heads init classifiers to zeros → softmax pinned at 0.5)."""
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, size, size, chans))
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: a + jnp.asarray(
            0.02 * rng.standard_normal(np.shape(a)).astype(np.float32)
        ).astype(a.dtype),
        variables)


def _cfg(**kw):
    kw.setdefault("image_size", _SIZE)
    kw.setdefault("img_num", _NUM)
    kw.setdefault("buckets", (1, 4))
    kw.setdefault("stream_ttl_s", 0.0)          # no evictor in tests
    kw.setdefault("max_inflight_windows", 16)
    return StreamConfig(**kw)


@pytest.fixture(scope="module")
def stack():
    cfg = _cfg()
    model = create_model(_MODEL, num_classes=2, in_chans=3 * _NUM)
    variables = _perturbed_variables(model, _SIZE, 3 * _NUM)
    serving_metrics = ServingMetrics()
    engine = InferenceEngine(model, variables, image_size=_SIZE,
                             img_num=_NUM, buckets=cfg.buckets,
                             metrics=serving_metrics, wire="float32")
    batcher = MicroBatcher(max_batch=4, deadline_ms=5.0, max_queue=64,
                           metrics=serving_metrics)
    engine.start(batcher)
    metrics = StreamingMetrics()
    manager_box = []
    dispatcher = WindowDispatcher(
        batcher, max_pending=cfg.max_inflight_windows,
        request_timeout_s=10.0,
        on_result=lambda j, s, e: manager_box[0].on_result(j, s, e),
        on_drop=lambda j, r: manager_box[0].on_drop(j, r))
    manager = StreamManager(cfg, dispatcher, metrics,
                            image_size=_SIZE, wire="float32")
    manager_box.append(manager)
    dispatcher.start()
    server = make_stream_server("127.0.0.1", 0, manager, engine,
                                serving_metrics, metrics)
    import threading
    threading.Thread(target=server.serve_forever,
                     kwargs={"poll_interval": 0.1}, daemon=True).start()
    port = server.server_address[1]
    yield type("Stack", (), dict(
        cfg=cfg, model=model, engine=engine, batcher=batcher,
        dispatcher=dispatcher, manager=manager, metrics=metrics,
        serving_metrics=serving_metrics, server=server, port=port))
    server.shutdown()
    manager.shutdown()
    dispatcher.stop()
    engine.stop()
    batcher.close()
    server.server_close()


# ---------------------------------------------------------------------------
# HTTP helpers
# ---------------------------------------------------------------------------

def _req(port, method, path, body=None, headers=None, timeout=30):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=body, method=method,
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        raw = r.read()
        return r.status, json.loads(raw) if raw[:1] in (b"{", b"[") \
            else raw.decode()


def _raw_frames(frames):
    """(body, headers) for the zero-decode x-dfd-raw chunk wire."""
    h, w = frames[0].shape[:2]
    return (b"".join(np.ascontiguousarray(f).tobytes() for f in frames),
            {"Content-Type": "application/x-dfd-raw",
             "X-Frame-Width": str(w), "X-Frame-Height": str(h)})


def _open_stream(port, stream_id=None):
    body = json.dumps({"stream_id": stream_id}).encode() if stream_id \
        else None
    status, obj = _req(port, "POST", "/streams", body,
                       {"Content-Type": "application/json"} if body else {})
    assert status == 201
    return obj["stream_id"]


def _wait_scored(port, sid, n, timeout=20):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        _, st = _req(port, "GET", f"/streams/{sid}")
        if st["counters"]["windows_scored"] >= n:
            return st
        time.sleep(0.02)
    raise AssertionError(f"stream {sid} never scored {n} windows: {st}")


# ---------------------------------------------------------------------------
# session lifecycle + scoring
# ---------------------------------------------------------------------------

def test_session_lifecycle_and_window_scoring(stack):
    port = stack.port
    assert _req(port, "GET", "/healthz")[0] == 200
    assert _req(port, "GET", "/readyz")[0] == 200
    sid = _open_stream(port)
    assert sid in _req(port, "GET", "/streams")[1]["streams"]

    rng = np.random.default_rng(0)
    frames = [rng.integers(0, 255, (_SIZE, _SIZE, 3), dtype=np.uint8)
              for _ in range(4)]                      # 2 windows (hop=2)
    body, headers = _raw_frames(frames)
    status, ack = _req(port, "POST", f"/streams/{sid}/frames", body,
                       headers)
    assert status == 200
    assert ack["frames_accepted"] == 4 and ack["decode_errors"] == 0
    assert ack["windows_emitted"] == 2
    assert ack["verdict"] in ("real", "suspect", "fake")

    st = _wait_scored(port, sid, 2)
    assert st["schema"].startswith("dfd.streaming.status.v")
    assert st["counters"]["frames_ingested"] == 4
    assert len(st["active_tracks"]) == 1              # full_frame: 1 track
    assert st["tracks"]["0"]["windows"] == 2
    assert st["stream"]["windows"] == 2

    status, final = _req(port, "DELETE", f"/streams/{sid}")
    assert status == 200 and final["closed"]
    assert _req(port, "GET", "/streams")[1]["active"] == 0
    with pytest.raises(urllib.error.HTTPError) as ei:
        _req(port, "GET", f"/streams/{sid}")
    assert ei.value.code == 404


def test_window_scores_bit_identical_to_cli_clip_path(stack):
    """The acceptance bar: a streamed window's score must equal scoring
    the same 12-channel-layout clip through the CLI path bit-for-bit.
    Raw-wire frames (no JPEG) at a non-canvas size, so BOTH paths run the
    full geometric preprocess on identical pixels."""
    port = stack.port
    sid = _open_stream(port)
    rng = np.random.default_rng(42)
    frames = [rng.integers(0, 255, (48, 40, 3), dtype=np.uint8)
              for _ in range(_NUM)]                   # exactly one window
    body, headers = _raw_frames(frames)
    assert _req(port, "POST", f"/streams/{sid}/frames", body,
                headers)[1]["windows_emitted"] == 1
    st = _wait_scored(port, sid, 1)
    got = st["stream"]["last_score"]

    from deepfake_detection_tpu.params import prepare_canvas
    clip = normalize_concat([prepare_canvas(f, _SIZE) for f in frames],
                            _NUM)[None]
    cli = make_score_fn(stack.model, stack.engine._variables)
    want = float(np.asarray(cli(jnp.asarray(clip)))[0, 0])
    assert got == want, f"stream {got!r} != CLI {want!r}"
    _req(port, "DELETE", f"/streams/{sid}")


def test_planted_vector_drives_hysteresis_transitions(stack):
    """The bench's verdict acceptance vector, in-process: windows ride
    the REAL engine, but verdicts consume the planted real→fake flip —
    transitions must land exactly where the EMA math says."""
    cfg = dataclasses.replace(stack.cfg, verdict_vector="0.05*2,0.95*6")
    manager = StreamManager(cfg, stack.dispatcher, stack.metrics,
                            image_size=_SIZE, wire="float32")
    s = manager.create("planted")
    rng = np.random.default_rng(1)
    for i in range(16):                               # 8 windows (hop=2)
        s.ingest_arrays([rng.integers(0, 255, (_SIZE, _SIZE, 3),
                                      dtype=np.uint8)])
    deadline = time.monotonic() + 20
    while s.windows_scored < 8 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert s.windows_scored == 8
    st = s.status()
    assert st["verdict"] == "fake"
    # stream-scope transitions: ema crosses 0.5 at window 4, 0.8 at 8
    stream_events = [e for e in st["events"] if e["scope"] == "stream"]
    assert [(e["from"], e["to"], e["windows"]) for e in stream_events] == \
        [("real", "suspect", 4), ("suspect", "fake", 8)]
    # the per-track machine saw the same flip
    track_events = [e for e in st["events"] if e["scope"] == "track"]
    assert [e["to"] for e in track_events] == ["suspect", "fake"]
    manager.close("planted")


def test_multipart_mjpeg_chunk_and_decode_error_accounting(stack):
    import io

    from PIL import Image
    port = stack.port
    sid = _open_stream(port, "mjpeg-test")
    rng = np.random.default_rng(3)
    buf = io.BytesIO()
    Image.fromarray(rng.integers(0, 255, (40, 40, 3), dtype=np.uint8)
                    ).save(buf, "JPEG", quality=90)
    good = buf.getvalue()
    parts = [good, b"THIS IS NOT A JPEG"]
    body = b"".join(
        b"--frame\r\nContent-Type: image/jpeg\r\n\r\n" + p + b"\r\n"
        for p in parts) + b"--frame--\r\n"
    status, ack = _req(
        port, "POST", f"/streams/{sid}/frames", body,
        {"Content-Type": "multipart/x-mixed-replace; boundary=frame"})
    assert status == 200
    assert ack["frames_accepted"] == 1 and ack["decode_errors"] == 1
    _req(port, "DELETE", f"/streams/{sid}")


def test_http_error_paths(stack):
    port = stack.port
    with pytest.raises(urllib.error.HTTPError) as ei:
        _req(port, "GET", "/streams/doesnotexist")
    assert ei.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        _req(port, "POST", "/streams/doesnotexist/frames", b"x",
             {"Content-Type": "application/octet-stream"})
    assert ei.value.code == 404
    sid = _open_stream(port, "dup")
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _open_stream(port, "dup")
        assert ei.value.code == 409
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(port, "POST", f"/streams/{sid}/frames", b"x" * 10,
                 {"Content-Type": "multipart/x-mixed-replace"})  # boundary?
        assert ei.value.code == 400
    finally:
        _req(port, "DELETE", f"/streams/{sid}")
    with pytest.raises(urllib.error.HTTPError) as ei:
        _req(port, "DELETE", "/streams/dup")
    assert ei.value.code == 404


def test_metrics_exposes_streaming_and_serving_catalogs(stack):
    status, text = _req(stack.port, "GET", "/metrics")
    assert status == 200
    # streaming catalog live alongside the serving one (one scrape = whole
    # pipeline), with the drop/shed counters present (never silent)
    for name in ("dfd_streaming_frames_ingested_total",
                 "dfd_streaming_windows_scored_total",
                 "dfd_streaming_windows_dropped_total",
                 "dfd_streaming_windows_shed_total",
                 "dfd_streaming_active_streams",
                 'dfd_streaming_latency_seconds_bucket{stage="score"',
                 "dfd_serving_batches_total",
                 "dfd_serving_backend_compiles_total"):
        assert name in text, name


def test_idle_stream_ttl_eviction(stack):
    cfg = dataclasses.replace(stack.cfg, stream_ttl_s=0.2)
    manager = StreamManager(cfg, stack.dispatcher, stack.metrics,
                            image_size=_SIZE, wire="float32")
    manager.create("idle")
    evicted0 = stack.metrics.streams_evicted_total.value
    manager.start_evictor()
    try:
        deadline = time.monotonic() + 10
        while manager.get("idle") is not None and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        assert manager.get("idle") is None, "idle stream never evicted"
        assert stack.metrics.streams_evicted_total.value == evicted0 + 1
    finally:
        manager.shutdown()


# ---------------------------------------------------------------------------
# fresh-interpreter runner e2e (the chaos-tier idiom: a native fault can
# at worst fail this one test)
# ---------------------------------------------------------------------------

_RUNNER_DRIVER = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
if cache:
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from deepfake_detection_tpu.runners.stream import main
main(sys.argv[1:])
"""


def test_runner_stream_subprocess_e2e(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        jax.config.jax_compilation_cache_dir or "")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = 18379
    proc = subprocess.Popen(
        [sys.executable, "-c", _RUNNER_DRIVER,
         "--model", _MODEL, "--image-size", str(_SIZE),
         "--img-num", str(_NUM), "--buckets", "1,4",
         "--port", str(port), "--verdict-vector", "0.05*2,0.95*6",
         "--event-log-dir", str(tmp_path)],
        cwd=repo, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        deadline = time.monotonic() + 120
        ready = False
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                break
            try:
                if _req(port, "GET", "/readyz", timeout=2)[0] == 200:
                    ready = True
                    break
            except (urllib.error.URLError, ConnectionError, OSError):
                time.sleep(0.2)
        assert ready, (f"runner never ready rc={proc.poll()}\n"
                       f"{proc.stderr.read() if proc.poll() is not None else ''}")

        sid = _open_stream(port, "e2e")
        rng = np.random.default_rng(5)
        frames = [rng.integers(0, 255, (_SIZE, _SIZE, 3), dtype=np.uint8)
                  for _ in range(16)]
        body, headers = _raw_frames(frames)
        status, ack = _req(port, "POST", f"/streams/{sid}/frames", body,
                           headers)
        assert status == 200 and ack["frames_accepted"] == 16
        st = _wait_scored(port, sid, 8, timeout=60)
        assert st["verdict"] == "fake"                # planted flip landed
        status, text = _req(port, "GET", "/metrics")
        assert "dfd_streaming_windows_scored_total" in text
        status, final = _req(port, "DELETE", f"/streams/{sid}")
        assert status == 200
        # schema-versioned events landed in the JSONL sink
        log = tmp_path / "e2e.events.jsonl"
        assert log.exists()
        events = [json.loads(ln) for ln in
                  log.read_text().strip().splitlines()]
        assert any(e["to"] == "fake" for e in events)
        assert all(e["schema"].startswith("dfd.streaming.verdict.v")
                   for e in events)
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# ffmpeg soft dependency
# ---------------------------------------------------------------------------

def test_container_ingest_501_without_ffmpeg(stack):
    from deepfake_detection_tpu.streaming.ingest import FfmpegDemuxer
    if FfmpegDemuxer.available():
        pytest.skip("ffmpeg installed: the 501 soft-dep path is inert")
    sid = _open_stream(stack.port, "container")
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(stack.port, "POST", f"/streams/{sid}/frames",
                 b"\x00" * 64, {"Content-Type": "video/mp4"})
        assert ei.value.code == 501
        assert "ffmpeg" in json.loads(ei.value.read())["error"]
    finally:
        _req(stack.port, "DELETE", f"/streams/{sid}")


def test_ffmpeg_demuxer_roundtrip(stack):
    """Container chunks → frames via the per-session ffmpeg subprocess
    (runs only where the soft dependency is installed)."""
    from deepfake_detection_tpu.streaming.ingest import (FfmpegDemuxer,
                                                         decode_frame_bytes)
    if not FfmpegDemuxer.available():
        pytest.skip("no ffmpeg binary on PATH (soft dependency)")
    import io

    from PIL import Image
    rng = np.random.default_rng(8)
    raw = b"".join(
        _jpeg_bytes_for_ffmpeg(Image, io, rng) for _ in range(6))
    d = FfmpegDemuxer()
    d.feed(raw)                       # MJPEG in → MJPEG out (re-encoded)
    frames = d.poll_frames(wait_s=5.0) + d.close()
    assert len(frames) == 6
    for f in frames:
        arr = decode_frame_bytes(f)
        assert arr is not None and arr.shape[2] == 3


def _jpeg_bytes_for_ffmpeg(Image, io, rng):
    buf = io.BytesIO()
    Image.fromarray(rng.integers(0, 255, (32, 32, 3), dtype=np.uint8)
                    ).save(buf, "JPEG", quality=90)
    return buf.getvalue()


# ---------------------------------------------------------------------------
# bench smoke (slow tier: subprocess server + load phases)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bench_stream_smoke(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "bench.md"
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "bench_stream.py"),
         "--smoke", "--image-size", "32", "--img-num", "2",
         "--buckets", "1,4", "--out", str(out)],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    text = out.read_text()
    assert "PASS" in text                      # verdict probe
    assert "delta across every load/probe phase = **0**" in text


# ---------------------------------------------------------------------------
# container demux death over HTTP (ISSUE 10 satellite): counted per-stream
# error + reset, never a hang
# ---------------------------------------------------------------------------

def test_container_demux_death_counted_and_reset_over_http(
        stack, tmp_path, monkeypatch):
    """ffmpeg dying mid-stream surfaces as a 422 with the demuxer reset
    and ``dfd_streaming_demux_failures_total`` + the per-stream counter
    moving; the session stays usable and closes cleanly."""
    import io

    from PIL import Image
    from test_streaming import _stub_ffmpeg

    from deepfake_detection_tpu.streaming import ingest as ingest_mod

    stub = _stub_ffmpeg(tmp_path)

    class StubDemuxer(ingest_mod.FfmpegDemuxer):
        @staticmethod
        def available(binary="ffmpeg"):
            return True

        def __init__(self, binary="ffmpeg"):
            super().__init__(binary=str(stub))

    monkeypatch.setattr(ingest_mod, "FfmpegDemuxer", StubDemuxer)
    port = stack.port
    sid = _open_stream(port, "demux-kill")
    rng = np.random.default_rng(3)
    buf = io.BytesIO()
    Image.fromarray(rng.integers(0, 255, (_SIZE, _SIZE, 3),
                                 dtype=np.uint8)).save(buf, "JPEG",
                                                       quality=90)
    jpeg = buf.getvalue()
    headers = {"Content-Type": "video/mp4"}
    status, ack = _req(port, "POST", f"/streams/{sid}/frames",
                       jpeg * 2, headers)
    assert status == 200                      # passthrough stub: frames
    assert ack["frames_accepted"] == 2        # surface like real ffmpeg
    session = stack.manager.get(sid)
    failures0 = stack.metrics.demux_failures_total.value
    session.demuxer._proc.kill()              # ffmpeg dies mid-stream
    session.demuxer._proc.wait(timeout=10)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _req(port, "POST", f"/streams/{sid}/frames", jpeg, headers)
    assert ei.value.code == 422               # surfaced, not hung
    assert stack.metrics.demux_failures_total.value == failures0 + 1
    _, st = _req(port, "GET", f"/streams/{sid}")
    assert st["counters"]["demux_failures"] == 1
    assert session.demuxer is None            # reset for the next chunk
    # the stream stays usable: the next container chunk gets a fresh
    # demuxer, and close-flush is safe
    status, ack = _req(port, "POST", f"/streams/{sid}/frames", jpeg,
                       headers)
    assert status == 200 and ack["frames_accepted"] == 1
    status, final = _req(port, "DELETE", f"/streams/{sid}")
    assert status == 200 and final["counters"]["demux_failures"] == 1
