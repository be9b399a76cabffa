"""Serving subsystem tests (ISSUE 2): bucket padding bit-identity, deadline
flush, load shedding, hot reload, and an end-to-end localhost round trip.

Fast tier (``serving`` marker, not ``slow``): everything runs against a
small conv model at a 32² canvas so the bucket compiles stay cheap and hit
the persistent compilation cache on reruns.
"""

import base64
import io
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from PIL import Image

from deepfake_detection_tpu.models import create_model, init_model
from deepfake_detection_tpu.models.helpers import save_model_checkpoint
from deepfake_detection_tpu.params import (make_score_fn, normalize_replicate,
                                           prepare_canvas)
from deepfake_detection_tpu.serving.batcher import (DeadlineExceeded,
                                                    MicroBatcher, QueueFull,
                                                    pick_bucket)
from deepfake_detection_tpu.serving.engine import InferenceEngine
from deepfake_detection_tpu.serving.http import (make_server,
                                                 serve_forever_in_thread)
from deepfake_detection_tpu.serving.metrics import ServingMetrics

pytestmark = pytest.mark.serving

_MODEL = "mobilenetv3_small_100"
_SIZE = 32


def _perturbed_variables(model, size, chans, seed=0):
    """Random init with every param nudged so class scores are
    discriminative (several zoo heads init their classifier to zeros,
    which would make every softmax exactly 0.5)."""
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, size, size, chans))
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: a + jnp.asarray(
            0.02 * rng.standard_normal(np.shape(a)).astype(np.float32)
        ).astype(a.dtype),
        variables)


def _canvases(n, size=_SIZE, seed=0):
    rng = np.random.default_rng(seed)
    return [prepare_canvas(
        rng.integers(0, 255, (96, 80, 3), dtype=np.uint8), size)
        for _ in range(n)]


def _payloads(n, size=_SIZE, seed=0, num=1):
    """float32-wire request payloads (the default wire's full CLI
    preprocess)."""
    return [normalize_replicate(c, num) for c in _canvases(n, size, seed)]


def _jpeg_bytes(seed=0, wh=64):
    rng = np.random.default_rng(seed)
    buf = io.BytesIO()
    Image.fromarray(rng.integers(0, 255, (wh, wh, 3), dtype=np.uint8)
                    ).save(buf, "JPEG", quality=90)
    return buf.getvalue()


# ---------------------------------------------------------------------------
# session serving stack: one engine + batcher + HTTP server for the file
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stack():
    model = create_model(_MODEL, num_classes=2, in_chans=3)
    variables = _perturbed_variables(model, _SIZE, 3)
    metrics = ServingMetrics()
    engine = InferenceEngine(model, variables, image_size=_SIZE, img_num=1,
                             buckets=(1, 4, 16), metrics=metrics)
    batcher = MicroBatcher(max_batch=16, deadline_ms=30.0, max_queue=64,
                           metrics=metrics)
    engine.start(batcher)
    server = make_server("127.0.0.1", 0, engine, batcher, metrics,
                         request_timeout_s=10.0)
    serve_forever_in_thread(server)
    port = server.server_address[1]
    yield type("Stack", (), dict(model=model, variables=variables,
                                 metrics=metrics, engine=engine,
                                 batcher=batcher, server=server, port=port))
    server.shutdown()
    engine.stop()
    batcher.close()
    server.server_close()


def _post(port, path, body, ctype, timeout=30):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", data=body,
                                 headers={"Content-Type": ctype})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(port, path, timeout=10):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as r:
        return r.status, r.read().decode()


# ---------------------------------------------------------------------------
# bucket padding
# ---------------------------------------------------------------------------

def test_pick_bucket():
    assert pick_bucket(1, (1, 4, 16)) == 1
    assert pick_bucket(2, (1, 4, 16)) == 4
    assert pick_bucket(4, (1, 4, 16)) == 4
    assert pick_bucket(16, (1, 4, 16)) == 16
    with pytest.raises(ValueError):
        pick_bucket(17, (1, 4, 16))


def test_bucket_padded_scores_bit_identical_to_unpadded(stack):
    """Padding rows are masked out of results and cannot perturb real
    rows: the same 3 requests score bit-for-bit whether they ride a
    zero-padded bucket-4 batch or an unpadded (all-real-rows) one — and
    the scores are independent of WHAT fills the pad slots."""
    payloads = _payloads(4)
    padded = stack.engine.score_batch(payloads[:3])   # 3 -> bucket 4 + pad
    assert padded.shape == (3, 2)
    unpadded = stack.engine.score_batch(payloads)     # full bucket 4
    np.testing.assert_array_equal(padded, unpadded[:3])
    # pad-slot content is irrelevant: replace the zero pad with real data
    other = stack.engine.score_batch(payloads[:3] + _payloads(1, seed=99))
    np.testing.assert_array_equal(padded, other[:3])
    # softmax rows are probabilities
    assert np.allclose(padded.sum(axis=1), 1.0, atol=1e-5)


def test_scores_stable_across_buckets(stack):
    """Which bucket a request rides is a compile-cache detail: bucket
    executables agree to float32 resolution.  (Bitwise equality across
    DIFFERENT batch shapes is not an XLA guarantee — its batch-size-
    dependent vectorization can shift the last ulp — which is exactly why
    the padding test above compares within one bucket.)"""
    payloads = _payloads(16, seed=3)
    b1 = stack.engine.score_batch(payloads[:1])
    b4 = stack.engine.score_batch(payloads[:4])
    b16 = stack.engine.score_batch(payloads)
    np.testing.assert_allclose(b1, b4[:1], rtol=0, atol=1e-6)
    np.testing.assert_allclose(b4, b16[:4], rtol=0, atol=1e-6)


def test_server_scores_match_cli_preprocess_exactly(stack):
    """Server scores must reproduce ``runners/test.py::preprocess`` +
    ``params.make_score_fn`` (the CLI path) bit-for-bit: both compile the
    same variables-as-argument program, so the b1 executables are
    identical."""
    from deepfake_detection_tpu.runners.test import preprocess

    jpeg = _jpeg_bytes(seed=3)
    canvas = prepare_canvas(
        np.asarray(Image.open(io.BytesIO(jpeg)).convert("RGB"), np.uint8),
        _SIZE)
    server_scores = stack.engine.score_batch(
        [normalize_replicate(canvas, 1)])
    cli = make_score_fn(stack.model, stack.engine._variables)
    cli_scores = np.asarray(cli(jnp.asarray(
        preprocess(io.BytesIO(jpeg), _SIZE, num=1))))
    np.testing.assert_array_equal(server_scores, cli_scores)


def test_uint8_wire_device_prologue_matches_host_preprocess():
    """The uint8 wire (deployment mode: device-side normalize + ×img_num
    replicate, the training loader's prologue idiom) must track the CLI's
    host preprocess to float32 resolution.  Cross-program fusion allows
    ulp-level drift, which is why the bit-exact float32 wire is the
    default — this pins the uint8 wire's drift bound."""
    size, num = 24, 2
    model = create_model(_MODEL, num_classes=2, in_chans=3 * num)
    variables = _perturbed_variables(model, size, 3 * num, seed=7)
    engine = InferenceEngine(model, variables, image_size=size, img_num=num,
                             buckets=(1, 2), wire="uint8")
    canvases = [prepare_canvas(
        np.random.default_rng(i).integers(0, 255, (48, 40, 3),
                                          dtype=np.uint8), size)
        for i in range(2)]
    got = engine.score_batch(canvases)                # uint8 in
    x = jnp.asarray(np.stack([normalize_replicate(c, num)
                              for c in canvases]))
    want = np.asarray(jax.jit(
        lambda v, xx: jax.nn.softmax(model.apply(v, xx, training=False), -1)
    )(engine._variables, x))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_zero_recompiles_across_mixed_batch_sizes(stack):
    """Every batch size up to the largest bucket runs on the startup
    executables — asserted on jax's OWN backend-compile monitoring hook,
    not just the engine's build counter (which by construction only moves
    in warmup)."""
    from deepfake_detection_tpu.serving.metrics import backend_compile_count

    warm = stack.engine.compile_count
    assert warm == 3                      # buckets (1, 4, 16)
    backend0 = backend_compile_count()
    for n in (1, 2, 3, 4, 5, 11, 16):
        scores = stack.engine.score_batch(_payloads(n, seed=n))
        assert scores.shape == (n, 2)
    assert stack.engine.compile_count == warm
    assert backend_compile_count() == backend0    # no silent XLA compile
    with pytest.raises(ValueError):       # beyond max bucket: hard error,
        stack.engine.score_batch(_payloads(17))   # never a silent compile
    assert stack.engine.compile_count == warm
    assert backend_compile_count() == backend0


# ---------------------------------------------------------------------------
# multi-frame wire path (ISSUE 8 satellite): img_num DISTINCT frames
# channel-concatenate into one temporal clip
# ---------------------------------------------------------------------------

def test_float32_wire_concat_of_identical_bit_identical_to_replicate():
    """The parity contract: a clip of img_num copies of one frame must
    score bit-identically to the single-frame replicate path.  On the
    float32 wire this is structural — ``normalize_concat`` of identical
    frames IS ``normalize_replicate`` byte-for-byte, and both payloads
    ride the same compiled bucket program."""
    from deepfake_detection_tpu.params import normalize_concat

    size, num = 24, 2
    model = create_model(_MODEL, num_classes=2, in_chans=3 * num)
    variables = _perturbed_variables(model, size, 3 * num, seed=3)
    engine = InferenceEngine(model, variables, image_size=size,
                             img_num=num, buckets=(1, 2), wire="float32")
    canvas = prepare_canvas(np.random.default_rng(0).integers(
        0, 255, (48, 40, 3), dtype=np.uint8), size)
    np.testing.assert_array_equal(normalize_concat([canvas] * num),
                                  normalize_replicate(canvas, num))
    rep = engine.score_batch([normalize_replicate(canvas, num)])
    cat = engine.score_batch([normalize_concat([canvas] * num)])
    np.testing.assert_array_equal(rep, cat)
    # distinct frames actually flow into distinct channels
    other = prepare_canvas(np.random.default_rng(9).integers(
        0, 255, (48, 40, 3), dtype=np.uint8), size)
    distinct = engine.score_batch([normalize_concat([canvas, other])])
    assert not np.array_equal(rep, distinct)


def test_uint8_wire_multi_frame_program_bit_identical_to_replicate():
    """uint8 wire: the multi-frame executable (normalize with ×img_num
    tiled mean/std, no in-program replication) must reproduce the
    replicate executable bit-for-bit on a clip of identical frames —
    the prologues are elementwise-identical arithmetic, and the model
    subprogram is the same HLO."""
    size, num = 24, 2
    model = create_model(_MODEL, num_classes=2, in_chans=3 * num)
    variables = _perturbed_variables(model, size, 3 * num, seed=3)
    engine = InferenceEngine(model, variables, image_size=size,
                             img_num=num, buckets=(1, 2), wire="uint8")
    assert engine.multi_frame
    assert engine.compile_count == 4          # 2 buckets × {rep, multi}
    canvas = prepare_canvas(np.random.default_rng(1).integers(
        0, 255, (48, 40, 3), dtype=np.uint8), size)
    rep = engine.score_batch([canvas])
    cat = engine.score_batch([np.concatenate([canvas] * num, axis=-1)])
    np.testing.assert_array_equal(rep, cat)
    # unknown channel widths are a hard error, never a silent compile
    with pytest.raises(ValueError):
        engine.score_batch([np.zeros((size, size, 9), np.uint8)])


def test_uint8_wire_mixed_single_and_multi_batch_splits_correctly():
    """A coalesced batch mixing single-frame and multi-frame requests
    splits into per-width sub-batches; every request resolves with the
    scores of its own group's bucket (bitwise — same bucket, same
    program; solo bucket-1 calls may differ in the last ulp, which is the
    documented cross-bucket caveat)."""
    size, num = 24, 2
    model = create_model(_MODEL, num_classes=2, in_chans=3 * num)
    variables = _perturbed_variables(model, size, 3 * num, seed=3)
    engine = InferenceEngine(model, variables, image_size=size,
                             img_num=num, buckets=(1, 2, 4), wire="uint8")
    batcher = MicroBatcher(max_batch=4, deadline_ms=20.0, max_queue=16,
                           metrics=engine.metrics)
    try:
        rng = np.random.default_rng(5)
        singles = [prepare_canvas(rng.integers(0, 255, (40, 36, 3),
                                               dtype=np.uint8), size)
                   for _ in range(2)]
        multis = [np.concatenate(
            [prepare_canvas(rng.integers(0, 255, (40, 36, 3),
                                         dtype=np.uint8), size)
             for _ in range(num)], axis=-1) for _ in range(2)]
        want = list(engine.score_batch(singles)) + \
            list(engine.score_batch(multis))
        # queue everything BEFORE the worker starts so all four coalesce
        # into ONE mixed batch deterministically
        reqs = [batcher.submit(a, timeout_s=10)
                for a in singles + multis]
        engine.start(batcher)
        got = [r.result(timeout=10) for r in reqs]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
    finally:
        engine.stop()
        batcher.close()


def test_http_multi_frame_clip_scoring(stack):
    """JSON ``frames_b64`` transport: img_num identical frames reproduce
    the single-frame score exactly; a wrong frame count is a 400.
    (The module stack runs img_num=1, so 'multi' degenerates to a
    1-element list — the dedicated engines above cover img_num>1; here
    the wire plumbing + validation are under test.)"""
    jpeg = _jpeg_bytes(seed=21)
    status, single = _post(stack.port, "/score", jpeg, "image/jpeg")
    assert status == 200 and single["frames"] == 1
    payload = json.dumps(
        {"frames_b64": [base64.b64encode(jpeg).decode()]}).encode()
    status, multi = _post(stack.port, "/score", payload,
                          "application/json")
    assert status == 200 and multi["frames"] == 1
    assert multi["fake_score"] == single["fake_score"]
    # frame count must be 1 or img_num (=1 here): 2 frames is a 400
    bad = json.dumps({"frames_b64": [base64.b64encode(jpeg).decode()] * 2
                      }).encode()
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(stack.port, "/score", bad, "application/json")
    assert ei.value.code == 400


def test_http_multipart_clip_matches_json_clip():
    """End-to-end multi-frame HTTP parity on an img_num=2 float32 server:
    multipart parts and JSON frames_b64 land identical scores, and a clip
    of identical frames equals the replicate path exactly."""
    size, num = 24, 2
    model = create_model(_MODEL, num_classes=2, in_chans=3 * num)
    variables = _perturbed_variables(model, size, 3 * num, seed=11)
    metrics = ServingMetrics()
    engine = InferenceEngine(model, variables, image_size=size,
                             img_num=num, buckets=(1, 2), metrics=metrics,
                             wire="float32")
    batcher = MicroBatcher(max_batch=2, deadline_ms=10.0, max_queue=8,
                           metrics=metrics)
    engine.start(batcher)
    server = make_server("127.0.0.1", 0, engine, batcher, metrics,
                         request_timeout_s=10.0)
    serve_forever_in_thread(server)
    port = server.server_address[1]
    try:
        j1, j2 = _jpeg_bytes(seed=1), _jpeg_bytes(seed=2)
        payload = json.dumps({"frames_b64": [
            base64.b64encode(j).decode() for j in (j1, j2)]}).encode()
        status, via_json = _post(port, "/score", payload,
                                 "application/json")
        assert status == 200 and via_json["frames"] == 2
        body = b"".join(
            b"--clip\r\nContent-Type: image/jpeg\r\n\r\n" + j + b"\r\n"
            for j in (j1, j2)) + b"--clip--\r\n"
        status, via_mp = _post(port, "/score", body,
                               "multipart/form-data; boundary=clip")
        assert status == 200 and via_mp["frames"] == 2
        assert via_mp["fake_score"] == via_json["fake_score"]
        # identical-frames clip == replicate path, over HTTP
        rep_status, rep = _post(port, "/score", j1, "image/jpeg")
        same = json.dumps({"frames_b64": [
            base64.b64encode(j1).decode()] * 2}).encode()
        status, cat = _post(port, "/score", same, "application/json")
        assert cat["fake_score"] == rep["fake_score"]
        assert cat["scores"] == rep["scores"]
    finally:
        server.shutdown()
        engine.stop()
        batcher.close()
        server.server_close()


# ---------------------------------------------------------------------------
# micro-batching behavior
# ---------------------------------------------------------------------------

def test_deadline_triggered_partial_batch_flush(stack):
    """3 requests (< the 4-bucket) must flush as ONE padded batch once the
    deadline window runs out, not wait for a full bucket."""
    m = stack.metrics
    batches0 = m.batches_total.value
    padded0 = m.padded_rows_total.value
    reqs = [stack.batcher.submit(p, timeout_s=10) for p in _payloads(3)]
    scores = [r.result(timeout=10) for r in reqs]
    assert all(s.shape == (2,) for s in scores)
    assert m.batches_total.value == batches0 + 1      # one coalesced batch
    assert m.padded_rows_total.value == padded0 + 1   # 3 -> bucket 4
    # per-request timings were stamped by the engine
    assert all("device" in r.timings and "queue" in r.timings for r in reqs)


def test_request_deadline_expires_in_queue():
    """A request whose per-request deadline passes while queued is failed
    at dequeue time and never reaches the device."""
    metrics = ServingMetrics()
    b = MicroBatcher(max_batch=4, deadline_ms=1.0, max_queue=8,
                     metrics=metrics)
    req = b.submit(np.zeros((4, 4, 3), np.uint8), timeout_s=0.01)
    time.sleep(0.05)
    assert b.take(timeout=0.0) is None    # expired request was dropped
    with pytest.raises(DeadlineExceeded):
        req.result(timeout=1.0)
    assert metrics.deadline_total.value == 1


def test_load_shedding_queue_full():
    metrics = ServingMetrics()
    b = MicroBatcher(max_batch=4, deadline_ms=1.0, max_queue=3,
                     metrics=metrics)
    for _ in range(3):
        b.submit(np.zeros((4, 4, 3), np.uint8))
    with pytest.raises(QueueFull) as ei:
        b.submit(np.zeros((4, 4, 3), np.uint8))
    assert ei.value.retry_after_s > 0
    assert metrics.shed_total.value == 1
    assert b.depth == 3                   # shed submit did not enqueue


def test_http_429_with_retry_after_when_overloaded(stack):
    """HTTP front end sheds with 429 + Retry-After once the queue is full:
    a private batcher nobody drains, 2 slots, 3 concurrent posts."""
    priv_metrics = ServingMetrics()
    batcher = MicroBatcher(max_batch=4, deadline_ms=5.0, max_queue=2,
                           metrics=priv_metrics)
    server = make_server("127.0.0.1", 0, stack.engine, batcher,
                         priv_metrics, request_timeout_s=1.0)
    serve_forever_in_thread(server)
    port = server.server_address[1]
    jpeg = _jpeg_bytes()
    try:
        fillers = [threading.Thread(
            target=lambda: _post_swallow(port, jpeg), daemon=True)
            for _ in range(2)]
        for t in fillers:
            t.start()
        deadline = time.monotonic() + 5.0
        while batcher.depth < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert batcher.depth == 2
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, "/score", jpeg, "image/jpeg", timeout=5)
        assert ei.value.code == 429
        assert int(ei.value.headers["Retry-After"]) >= 1
        assert priv_metrics.shed_total.value == 1
    finally:
        server.shutdown()
        batcher.close()
        server.server_close()


def _post_swallow(port, jpeg):
    try:
        _post(port, "/score", jpeg, "image/jpeg", timeout=30)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# hot weight reload
# ---------------------------------------------------------------------------

def test_hot_reload_picks_up_new_checkpoint(tmp_path):
    model = create_model(_MODEL, num_classes=2, in_chans=3)
    variables = _perturbed_variables(model, _SIZE, 3, seed=1)
    engine = InferenceEngine(model, variables, image_size=_SIZE, img_num=1,
                             buckets=(1,))
    batcher = MicroBatcher(max_batch=1, deadline_ms=1.0, max_queue=8,
                           metrics=engine.metrics)
    engine.start(batcher)
    try:
        payload = _payloads(1, seed=5)[0]
        before = engine.score_batch([payload])

        engine.start_reload_watcher(str(tmp_path), interval_s=0.05)
        new_vars = _perturbed_variables(model, _SIZE, 3, seed=2)
        save_model_checkpoint(str(tmp_path / "model_new.msgpack"),
                              jax.tree.map(np.asarray, new_vars))
        deadline = time.monotonic() + 10.0
        while engine.reload_count == 0 and time.monotonic() < deadline:
            # the swap happens between batches — keep traffic flowing
            batcher.submit(payload, timeout_s=5).result(timeout=5)
        assert engine.reload_count == 1, "watcher never swapped the weights"

        after = engine.score_batch([payload])
        assert not np.array_equal(before, after)
        want = np.asarray(jax.jit(
            lambda v, x: jax.nn.softmax(
                model.apply(v, x, training=False), -1)
        )(jax.device_put(new_vars), jnp.asarray(payload[None])))
        np.testing.assert_array_equal(after, want)
        assert engine.metrics.reloads_total.value == 1
    finally:
        engine.stop()
        batcher.close()


def test_reload_rejects_mismatched_tree(tmp_path):
    model = create_model(_MODEL, num_classes=2, in_chans=3)
    variables = _perturbed_variables(model, _SIZE, 3, seed=1)
    engine = InferenceEngine(model, variables, image_size=_SIZE, img_num=1,
                             buckets=(1,))
    payload = _payloads(1, seed=5)[0]
    before = engine.score_batch([payload])
    bad = {"params": {"not_the_model": np.zeros((3, 3), np.float32)}}
    engine.submit_reload(bad, source="<test>")
    engine._maybe_apply_reload()
    assert engine.reload_count == 0
    assert engine.metrics.reload_errors_total.value == 1
    np.testing.assert_array_equal(engine.score_batch([payload]), before)


# ---------------------------------------------------------------------------
# end-to-end HTTP round trip
# ---------------------------------------------------------------------------

def test_readyz_carries_per_model_json_detail(stack):
    """ISSUE 15 satellite: the /readyz body is the per-model readiness
    JSON, so a fleet router can tell "cold model warming" (parseable
    503) from "engine down" (no response) without scraping metrics
    text."""
    status, body = _get(stack.port, "/readyz")
    assert status == 200
    detail = json.loads(body)
    assert detail["ready"] is True
    assert detail["breaker"] == "closed"
    primary = stack.engine.default_model_id
    assert primary in detail["models"]
    m = detail["models"][primary]
    assert m["warmed"] is True and m["image_size"] == _SIZE
    assert m["img_num"] == 1 and m["dtype"] == "f32"
    assert detail["queue_depth"] == stack.metrics.queue_depth
    # the not-ready body keeps the same shape (parseable 503): flip the
    # gauge through the metrics seam the canary/recovery paths use
    stack.metrics.ready = False
    try:
        import urllib.error
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(stack.port, "/readyz")
        assert ei.value.code == 503
        cold = json.loads(ei.value.read())
        assert cold["ready"] is False and primary in cold["models"]
    finally:
        stack.metrics.ready = True


def test_e2e_localhost_roundtrip(stack):
    from deepfake_detection_tpu.runners.test import preprocess

    port = stack.port
    assert _get(port, "/healthz")[0] == 200
    assert _get(port, "/readyz")[0] == 200

    jpeg = _jpeg_bytes(seed=11)
    status, body = _post(port, "/score", jpeg, "image/jpeg")
    assert status == 200
    assert 0.0 <= body["fake_score"] <= 1.0
    assert len(body["scores"]) == 2
    assert abs(sum(body["scores"]) - 1.0) < 1e-5
    assert set(body["timings_ms"]) == {"preprocess", "queue", "device",
                                       "total"}

    # identical score through the CLI preprocess + score path
    cli = make_score_fn(stack.model, stack.engine._variables)
    want = float(np.asarray(cli(jnp.asarray(
        preprocess(io.BytesIO(jpeg), _SIZE, num=1))))[0, 0])
    assert body["fake_score"] == want

    # JSON/base64 transport scores identically
    payload = json.dumps(
        {"image_b64": base64.b64encode(jpeg).decode()}).encode()
    status, body2 = _post(port, "/score", payload, "application/json")
    assert status == 200
    assert body2["fake_score"] == body["fake_score"]

    # malformed payload -> 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, "/score", b"not an image", "image/jpeg")
    assert ei.value.code == 400

    # metrics exposition carries the serving counters + histograms
    status, text = _get(port, "/metrics")
    assert status == 200
    assert "dfd_serving_compiles_total 3" in text
    assert 'dfd_serving_requests_total{status="200"}' in text
    assert 'dfd_serving_latency_seconds_bucket{stage="device",le="+Inf"}' \
        in text
    assert "dfd_serving_ready 1" in text


def test_unknown_route_404(stack):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(stack.port, "/nope")
    assert ei.value.code == 404


# ---------------------------------------------------------------------------
# worker crash recovery
# ---------------------------------------------------------------------------

def test_worker_crash_recovery(stack):
    """A poisoned request (bad array shape) must fail with 500-style error
    while the worker survives and keeps scoring the next requests."""
    restarts0 = stack.metrics.worker_restarts_total.value
    bad = stack.batcher.submit(np.zeros((7, 9, 3), np.uint8), timeout_s=10)
    with pytest.raises(Exception):
        bad.result(timeout=10)
    deadline = time.monotonic() + 5.0
    while stack.metrics.worker_restarts_total.value == restarts0 \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert stack.metrics.worker_restarts_total.value == restarts0 + 1
    # engine still serves
    ok = stack.batcher.submit(_payloads(1, seed=9)[0], timeout_s=10)
    assert ok.result(timeout=10).shape == (2,)


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

def test_serve_config_validation():
    from deepfake_detection_tpu.config import ServeConfig
    cfg = ServeConfig.from_args(["--buckets", "16,1,4,4"])
    assert cfg.buckets == (1, 4, 16)      # sorted, deduped
    assert cfg.max_batch_size == 16
    assert cfg.in_chans == 12             # img_num 4 default
    with pytest.raises(ValueError):
        ServeConfig(buckets=(0, 4))
    with pytest.raises(ValueError):
        ServeConfig(buckets=(1, 64), max_queue=32)   # queue < max bucket


# ---------------------------------------------------------------------------
# on-demand profiler capture through the engine worker (obs/profiler.py)
# ---------------------------------------------------------------------------

def test_profile_trigger_yields_one_bounded_capture_with_batch_spans(
        tmp_path):
    """A PROFILE file in the capture directory traces the worker's next
    device batches once: the trace holds dfd.serve.stage and
    dfd.serve.complete of the same ``batch``; with no trigger the idle
    path opens no session."""
    from jax.profiler import ProfileData

    from deepfake_detection_tpu.obs import ProfilerCapture

    class Counting(ProfilerCapture):
        polls = 0

        def poll(self):
            self.polls += 1
            super().poll()

    def wait_for(cond, what, seconds=20.0):
        deadline = time.monotonic() + seconds
        while not cond():
            assert time.monotonic() < deadline, f"timed out: {what}"
            time.sleep(0.01)

    model = create_model(_MODEL, num_classes=2, in_chans=3)
    variables = _perturbed_variables(model, _SIZE, 3, seed=1)
    engine = InferenceEngine(model, variables, image_size=_SIZE, img_num=1,
                             buckets=(1,))
    cap = engine.profiler = Counting(str(tmp_path), num_steps=2)
    batcher = MicroBatcher(max_batch=1, deadline_ms=1.0, max_queue=8,
                           metrics=engine.metrics)
    engine.start(batcher)
    try:
        payload = _payloads(1, seed=5)[0]
        batcher.submit(payload, timeout_s=5).result(timeout=5)
        seen = cap.polls
        wait_for(lambda: cap.polls >= seen + 2, "two idle loops")
        assert not cap.active and cap.captures_total == 0
        assert not (tmp_path / "profile").exists()

        (tmp_path / "PROFILE").touch()
        wait_for(lambda: cap.active, "the capture to start")
        assert not (tmp_path / "PROFILE").exists(), "trigger not consumed"
        for _ in range(3):      # the window is two device batches
            batcher.submit(payload, timeout_s=5).result(timeout=5)
        wait_for(lambda: cap.captures_total == 1, "the capture to stop")
        assert not cap.active
    finally:
        engine.stop()
        batcher.close()
    dirs = list((tmp_path / "profile").iterdir())
    assert len(dirs) == 1 and dirs[0].name.startswith("ondemand-")
    files = sorted(dirs[0].rglob("*.xplane.pb"))
    assert files, "the capture wrote no .xplane.pb"
    stats = {"dfd.serve.stage": [], "dfd.serve.complete": []}
    names = set()
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    names.add(ev.name)
                    if ev.name in stats:
                        stats[ev.name].append(dict(ev.stats))
    first = int(dirs[0].name.split("-")[1])
    staged = {st["batch"] for st in stats["dfd.serve.stage"]}
    assert staged == {first, first + 1}, "the window is not two batches"
    assert first in {st["batch"] for st in stats["dfd.serve.complete"]}
    assert all(st["rows"] == 1 and st["bucket"] == 1
               for st in stats["dfd.serve.stage"])
    assert {"dfd.serve.wait", "dfd.serve.gather"} <= names
