"""Warm-start serving tests (ISSUE 19): the persistent AOT executable
store, its paranoid fallback ladder, staged readiness, parallel warmup
overlap, and the autoscaler's standby-promotion books.

Fast tier (``warmstart`` marker): everything runs the small conv model at
a 32² canvas, same as test_serving.py.  The fresh-interpreter
zero-backend-compile e2e (the tentpole's headline contract) is slow-tier
because each subprocess pays a real cold start; the measured cold/warm/
standby comparison is ``tools/bench_serve.py --coldstart``.

Counting semantics under test (serving/metrics.py):

* entry absent                  → ``warmstart_misses_total``
* present but unusable          → ``warmstart_fallbacks_total`` (loud)
* deserialized                  → ``warmstart_hits_total``
* canary-rejected after a hit   → ``warmstart_canary_rejects_total``
  (then recompiled fresh and re-serialized over)
* store writes                  → ``warmstart_serialized_total``
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from deepfake_detection_tpu.models import create_model, init_model
from deepfake_detection_tpu.params import normalize_replicate, prepare_canvas
from deepfake_detection_tpu.serving import warmkey
from deepfake_detection_tpu.serving.batcher import MicroBatcher
from deepfake_detection_tpu.serving.engine import InferenceEngine
from deepfake_detection_tpu.serving.metrics import ServingMetrics
from deepfake_detection_tpu.serving.warmstart import (ExecutableStore,
                                                      WarmstartMiss)

pytestmark = pytest.mark.warmstart

_MODEL = "mobilenetv3_small_100"
_SIZE = 32


@pytest.fixture(autouse=True)
def _no_persistent_jax_cache():
    """conftest.py points jax at the suite's persistent compilation
    cache, but an executable LOADED from that cache serializes to a
    payload XLA refuses to deserialize (ExecutableStore.save detects
    and refuses it) — so the store-lifecycle tests here must compile
    for real.  Scoped per-test so the rest of the suite keeps the warm
    cache.  Flipping the config dir alone is NOT enough: jax memoizes
    the per-backend cache-used decision once (`_cache_checked`), so if
    any earlier test in the process compiled with the cache armed the
    dir=None update is silently ignored and these engines load from
    disk — whose executables serialize to Symbols-not-found payloads.
    reset_cache() drops the memo on both sides of the test."""
    from jax._src import compilation_cache as _cc
    prev = jax.config.jax_compilation_cache_dir
    _cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    _cc.reset_cache()


def _perturbed_variables(model, size, chans, seed=0):
    """Same idiom as test_serving.py: nudge every param so class scores
    are discriminative (zero-init classifier heads score 0.5 flat)."""
    import jax.numpy as jnp
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, size, size, chans))
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: a + jnp.asarray(
            0.02 * rng.standard_normal(np.shape(a)).astype(np.float32)
        ).astype(a.dtype),
        variables)


def _payloads(n, size=_SIZE, seed=0):
    rng = np.random.default_rng(seed)
    return [normalize_replicate(prepare_canvas(
        rng.integers(0, 255, (96, 80, 3), dtype=np.uint8), size), 1)
        for _ in range(n)]


# ---------------------------------------------------------------------------
# warmkey: jax-free key/manifest layer
# ---------------------------------------------------------------------------

def _fields(**over):
    base = dict(backend="cpu", device_kind="cpu", program="p" * 64,
                geometry={"image_size": 32, "img_num": 1},
                bucket=4, chans=3, wire="float32", quant="f32")
    base.update(over)
    return warmkey.key_fields(**base)


def test_store_key_deterministic_and_field_sensitive():
    k = warmkey.store_key(_fields())
    assert k == warmkey.store_key(_fields())          # pure function
    assert len(k) == 64
    # EVERY field is load-bearing: drifting any one orphans the entry
    for name, val in [("backend", "tpu"), ("device_kind", "TPU v4"),
                      ("program", "q" * 64), ("bucket", 8), ("chans", 12),
                      ("wire", "uint8"), ("quant", "int8"),
                      ("geometry", {"image_size": 64, "img_num": 1})]:
        assert warmkey.store_key(_fields(**{name: val})) != k, name
    # runtime versions are baked into the key (jax/jaxlib skew = miss)
    skew = _fields()
    skew["jax"] = "0.0.0"
    assert warmkey.store_key(skew) != k


def test_store_key_refuses_partial_fields():
    incomplete = _fields()
    del incomplete["device_kind"]
    with pytest.raises(ValueError, match="device_kind"):
        warmkey.store_key(incomplete)


def test_encode_decode_array_bit_exact():
    rng = np.random.default_rng(7)
    for arr in (rng.standard_normal((4, 2)).astype(np.float32),
                rng.integers(0, 256, (3, 5), dtype=np.uint8),
                np.array([np.nan, np.inf, -0.0], dtype=np.float64)):
        out = warmkey.decode_array(warmkey.encode_array(arr))
        assert out.dtype == arr.dtype and out.shape == arr.shape
        assert np.array_equal(arr.view(np.uint8), out.view(np.uint8))


def test_write_atomic_leaves_no_partials(tmp_path):
    p = str(tmp_path / "sub" / "blob.exe")
    warmkey.write_atomic(p, b"payload")
    assert open(p, "rb").read() == b"payload"
    warmkey.write_atomic(p, b"replaced")              # overwrite in place
    assert open(p, "rb").read() == b"replaced"
    assert [f for f in os.listdir(tmp_path / "sub")
            if f.endswith(".tmp")] == []


def test_manifest_roundtrip(tmp_path):
    p = str(tmp_path / "m.json")
    m = {"fields": _fields(), "key": "k", "params_fingerprint": "fp",
         "golden_scores": warmkey.encode_array(np.zeros((1, 2), np.float32))}
    warmkey.write_manifest(p, m)
    assert warmkey.read_manifest(p) == json.loads(json.dumps(m))


# ---------------------------------------------------------------------------
# store lifecycle against a real engine
# ---------------------------------------------------------------------------

_BUCKETS = (1, 2)


def _warm_engine(store, metrics=None, variables=None, **kw):
    model = create_model(_MODEL, num_classes=2, in_chans=3)
    if variables is None:
        variables = _perturbed_variables(model, _SIZE, 3)
    return InferenceEngine(model, variables, image_size=_SIZE, img_num=1,
                           buckets=_BUCKETS,
                           metrics=metrics or ServingMetrics(),
                           warmstart=store, **kw), variables


def _scores(engine, payloads):
    batcher = MicroBatcher(max_batch=max(_BUCKETS), deadline_ms=10.0,
                           max_queue=16, metrics=engine.metrics)
    engine.start(batcher)
    try:
        return np.asarray(engine.score_batch(payloads))
    finally:
        engine.stop()
        batcher.close()


def test_miss_serialize_hit_and_bit_identical_scores(tmp_path):
    """Cold engine populates the store (all misses, all serialized); a
    second engine over the same store deserializes everything (all hits,
    zero fresh compiles) and scores BIT-identically."""
    store = ExecutableStore(str(tmp_path))
    m1 = ServingMetrics()
    e1, variables = _warm_engine(store, m1)
    n_units = len(_BUCKETS)                           # float32 wire: 1 chans
    assert m1.warmstart_misses_total.value == n_units
    assert m1.warmstart_serialized_total.value == n_units
    assert m1.warmstart_hits_total.value == 0
    assert e1.compile_count == n_units
    fresh = _scores(e1, _payloads(2, seed=5))

    m2 = ServingMetrics()
    e2, _ = _warm_engine(store, m2, variables=variables)
    assert m2.warmstart_hits_total.value == n_units
    assert m2.warmstart_misses_total.value == 0
    assert m2.warmstart_fallbacks_total.value == 0
    assert m2.warmstart_canary_rejects_total.value == 0
    assert e2.compile_count == 0                      # no fresh compiles
    warm = _scores(e2, _payloads(2, seed=5))
    np.testing.assert_array_equal(fresh, warm)


def test_corrupt_blob_is_loud_counted_fallback_and_reserialized(tmp_path):
    """A corrupt payload under the right key: deserialize fails → counted
    fallback (NOT a silent miss), fresh compile, re-serialize over — and
    the next engine hits again."""
    store = ExecutableStore(str(tmp_path))
    _, variables = _warm_engine(store)
    for f in os.listdir(tmp_path):
        if f.endswith(".exe"):
            (tmp_path / f).write_bytes(b"garbage not a pickle")
    m2 = ServingMetrics()
    e2, _ = _warm_engine(store, m2, variables=variables)
    n_units = len(_BUCKETS)
    assert m2.warmstart_fallbacks_total.value == n_units
    assert m2.warmstart_hits_total.value == 0
    assert m2.warmstart_misses_total.value == 0
    assert e2.compile_count == n_units                # compiled fresh
    assert m2.warmstart_serialized_total.value == n_units  # healed store
    m3 = ServingMetrics()
    e3, _ = _warm_engine(store, m3, variables=variables)
    assert m3.warmstart_hits_total.value == n_units
    assert e3.compile_count == 0


def test_version_skew_manifest_is_key_mismatch_fallback(tmp_path):
    """A manifest whose echoed fields disagree with the derived key (the
    foreign-file / version-skew defense) falls back loudly."""
    store = ExecutableStore(str(tmp_path))
    _, variables = _warm_engine(store)
    for f in os.listdir(tmp_path):
        if f.endswith(".json"):
            m = json.loads((tmp_path / f).read_text())
            m["fields"]["jax"] = "0.0.0-foreign"
            (tmp_path / f).write_text(json.dumps(m))
    m2 = ServingMetrics()
    e2, _ = _warm_engine(store, m2, variables=variables)
    assert m2.warmstart_fallbacks_total.value == len(_BUCKETS)
    assert m2.warmstart_hits_total.value == 0
    assert e2.compile_count == len(_BUCKETS)


def test_store_load_reasons():
    """WarmstartMiss reasons drive the miss/fallback split — pin them."""
    with pytest.raises(WarmstartMiss) as e:
        ExecutableStore("/tmp/definitely-empty-warmstart-store").load(
            _fields(), execution_devices=jax.devices()[:1])
    assert e.value.reason == "absent"


def test_canary_rejects_tampered_golden_scores_and_recompiles(tmp_path):
    """Same checkpoint fingerprint + non-matching golden scores = the
    deserialized executable is computing something else: canary-reject,
    recompile fresh, re-serialize over.  The engine still comes up."""
    store = ExecutableStore(str(tmp_path))
    _, variables = _warm_engine(store)
    for f in os.listdir(tmp_path):
        if f.endswith(".json"):
            m = json.loads((tmp_path / f).read_text())
            ref = warmkey.decode_array(m["golden_scores"])
            m["golden_scores"] = warmkey.encode_array(ref + 0.5)
            (tmp_path / f).write_text(json.dumps(m))
    m2 = ServingMetrics()
    e2, _ = _warm_engine(store, m2, variables=variables)
    n_units = len(_BUCKETS)
    assert m2.warmstart_hits_total.value == n_units   # loads succeeded...
    assert m2.warmstart_canary_rejects_total.value == n_units  # ...gated
    assert e2.compile_count == n_units                # recompiled fresh
    assert m2.warmstart_serialized_total.value == n_units      # healed
    assert e2.ready
    # healed store passes the canary again
    m3 = ServingMetrics()
    e3, _ = _warm_engine(store, m3, variables=variables)
    assert m3.warmstart_canary_rejects_total.value == 0
    assert m3.warmstart_hits_total.value == n_units


def test_fingerprint_skew_passes_canary_and_restamps_manifest(tmp_path):
    """A DIFFERENT checkpoint of the same architecture shares executables
    (weights are call arguments): the load passes the finite/shape canary
    without the bit-exact gate, and the manifest is re-stamped so the
    next same-checkpoint spawn regains bit-exactness."""
    store = ExecutableStore(str(tmp_path))
    model = create_model(_MODEL, num_classes=2, in_chans=3)
    v1 = _perturbed_variables(model, _SIZE, 3, seed=1)
    v2 = _perturbed_variables(model, _SIZE, 3, seed=2)
    e1 = InferenceEngine(model, v1, image_size=_SIZE, img_num=1,
                         buckets=_BUCKETS, metrics=ServingMetrics(),
                         warmstart=store)
    fp1 = e1._models["default"].fingerprint
    m2 = ServingMetrics()
    e2 = InferenceEngine(model, v2, image_size=_SIZE, img_num=1,
                         buckets=_BUCKETS, metrics=m2, warmstart=store)
    assert m2.warmstart_hits_total.value == len(_BUCKETS)
    assert m2.warmstart_canary_rejects_total.value == 0
    fp2 = e2._models["default"].fingerprint
    assert fp1 != fp2
    stamped = {json.loads((tmp_path / f).read_text())["params_fingerprint"]
               for f in os.listdir(tmp_path) if f.endswith(".json")}
    assert stamped == {fp2}                           # re-stamped for v2


# ---------------------------------------------------------------------------
# staged readiness + parallel warmup
# ---------------------------------------------------------------------------

def test_staged_warmup_serves_priority_bucket_then_fills(tmp_path):
    """warmup(staged=True): /readyz flips 200 in phase ``degraded`` after
    only the priority bucket warmed; dispatch pads into the warm subset;
    the background thread fills the rest and flips phase ``ready``."""
    engine, _ = _warm_engine(None, warmup=False, warm_priority=(1,))
    assert engine.readiness_detail()["phase"] == "cold"
    assert not engine.ready
    engine.warmup(staged=True)
    # degraded is observable synchronously: warmup() returns after the
    # priority bucket only (the rest ride the background thread)
    detail = engine.readiness_detail()
    assert detail["ready"] is True
    entry = engine._models["default"]
    assert engine._warm_buckets(entry, 3)[0] == 1     # bucket 1 live
    engine._warm_thread.join(timeout=120)
    assert engine.readiness_detail()["phase"] == "ready"
    assert tuple(engine._warm_buckets(entry, 3)) == _BUCKETS
    scores = _scores(engine, _payloads(2, seed=3))
    assert scores.shape == (2, 2)


def test_degraded_dispatch_restricted_to_warm_buckets():
    """While only bucket 1 is warm, a 2-request batch must chunk through
    the warm bucket rather than touch (or worse, compile) bucket 2."""
    engine, _ = _warm_engine(None, warmup=False)
    entry = engine._models["default"]
    engine._warm_entry(entry, buckets=(1,))
    assert tuple(engine._warm_buckets(entry, 3)) == (1,)
    compiles0 = engine.compile_count
    engine._phase = "degraded"
    engine.metrics.ready = True
    # the async dispatch path chunks a coalesced group by the largest
    # LIVE bucket (here 1), so 3 requests ride 3 bucket-1 dispatches
    batcher = MicroBatcher(max_batch=4, deadline_ms=5.0, max_queue=16,
                           metrics=engine.metrics)
    engine.start(batcher)
    try:
        reqs = [batcher.submit(p, timeout_s=30)
                for p in _payloads(3, seed=11)]
        scores = [r.result(timeout=30) for r in reqs]
    finally:
        engine.stop()
        batcher.close()
    assert all(s.shape == (2,) for s in scores)
    assert engine.compile_count == compiles0          # no lazy compile


def test_parallel_warmup_wall_beats_sum_of_compile_walls():
    """ISSUE 19 satellite: with compilation parallelism the warmup wall
    must undercut the serial sum of per-unit compile walls (XLA's
    ``compile()`` releases the GIL, so bucket compiles overlap even on
    one core)."""
    model = create_model(_MODEL, num_classes=2, in_chans=3)
    variables = _perturbed_variables(model, _SIZE, 3)
    engine = InferenceEngine(model, variables, image_size=_SIZE,
                             img_num=1, buckets=(1, 2, 4, 8),
                             metrics=ServingMetrics(), warmup=False,
                             warm_parallel=4)
    engine.warmup()
    walls = engine.warm_compile_walls
    assert len(walls) == 4 and all(w > 0 for w in walls.values())
    assert engine.last_warmup_wall < 0.9 * sum(walls.values()), (
        engine.last_warmup_wall, walls)


# ---------------------------------------------------------------------------
# standby replicas: promotion books + capacity accounting
# ---------------------------------------------------------------------------

def _standby(netloc="127.0.0.1:7001", warmed=True, alive=True):
    from deepfake_detection_tpu.fleet.autoscaler import _Standby
    proc = SimpleNamespace(netloc=netloc, alive=alive,
                           proc=SimpleNamespace(returncode=None if alive
                                                else -9),
                           stop=lambda timeout_s=15: None)
    s = _Standby(proc, born_t=0.0)
    s.warmed = warmed
    return s


def _autoscaler(standby_replicas=0, tenant=None, **knob_over):
    from deepfake_detection_tpu.fleet.autoscaler import (Autoscaler,
                                                         PolicyKnobs)
    from deepfake_detection_tpu.fleet.controller import HealthScraper
    from deepfake_detection_tpu.fleet.metrics import RouterMetrics
    from deepfake_detection_tpu.fleet.registry import Registry
    knobs = dict(slo_p99_ms=100.0, min_replicas=1, max_replicas=3,
                 up_samples=2, down_samples=3, up_cooldown_s=5.0,
                 down_cooldown_s=10.0, shed_high=0.01, depth_high=8.0,
                 depth_low=1.0, p99_low_frac=0.5)
    knobs.update(knob_over)
    reg = Registry(["a:1"])
    r = reg.get("a:1")
    r.healthy = r.ready = True
    m = RouterMetrics()
    sc = HealthScraper(reg, m)
    a = Autoscaler(reg, m, sc, knobs=PolicyKnobs(**knobs),
                   standby_replicas=standby_replicas, tenant=tenant)
    return a, reg, m


def test_standby_promotion_books_no_spawn():
    """Promotion = registry add of an already-spawned child: booked as a
    scale-up + promotion, NOT a spawn (that was booked at park time), so
    spawned == retired + killed + live + standby stays exact."""
    a, reg, m = _autoscaler()
    a.standbys.append(_standby())
    assert a._promote_standby() is True
    assert "127.0.0.1:7001" in reg.ids()
    assert reg.get("127.0.0.1:7001").warming       # first scrape flips it
    assert m.standby_promotions_total.value == 1
    assert m.autoscale_up_total.value == 1
    assert m.replicas_spawned_total.value == 0
    assert m.standby_replicas == 0
    assert a.status()["books"]["standby_promotions"] == 1
    assert a.status()["standbys"]["parked"] == 0


def test_scale_up_prefers_warmed_standby_over_spawn():
    a, reg, m = _autoscaler()
    a.standbys.append(_standby(warmed=False))      # still compiling: skip
    a.standbys.append(_standby("127.0.0.1:7002", warmed=True))
    a._scale_up()
    assert "127.0.0.1:7002" in reg.ids()
    assert m.standby_promotions_total.value == 1
    assert m.replicas_spawned_total.value == 0     # no cold spawn paid
    assert len(a.standbys) == 1                    # unwarmed one stays


def test_dead_standby_reaped_and_booked_killed():
    a, _, m = _autoscaler()
    a.standbys.append(_standby(alive=False))
    a._tend_standbys()
    assert a.standbys == []
    assert m.replicas_killed_total.value == 1
    assert m.standby_replicas == 0


def test_parked_standby_holds_slot_against_backfill_tenant():
    """The backfill tenant must see a parked standby's slot as USED —
    otherwise promotion would have to evict a worker first, re-adding
    the latency the standby exists to remove."""
    calls = []
    tenant = SimpleNamespace(
        reconcile=lambda idle, total: calls.append((idle, total)),
        ensure_room=lambda idle: None, stop=lambda: None)
    a, _, _ = _autoscaler(tenant=tenant)
    a.standbys.append(_standby())
    a.tick(now=1.0)
    # max 3, 1 registered + 1 standby parked -> exactly 1 idle slot
    assert calls == [(1, 3)]


def test_stop_kills_standbys_and_zeroes_gauge():
    stopped = []
    a, _, m = _autoscaler()
    s = _standby()
    s.proc.stop = lambda timeout_s=15: stopped.append(True)
    a.standbys.append(s)
    a.stop()
    assert stopped == [True]
    assert a.standbys == [] and m.standby_replicas == 0
    assert m.replicas_killed_total.value == 1


# ---------------------------------------------------------------------------
# fresh-interpreter e2e: the zero-recompile second start (slow tier)
# ---------------------------------------------------------------------------

_E2E = r"""
import sys, numpy as np
from deepfake_detection_tpu.config import ServeConfig
from deepfake_detection_tpu.runners.serve import build_engine
from deepfake_detection_tpu.serving.metrics import backend_compile_count
cfg = ServeConfig.from_args([
    "--model", "{model}", "--image-size", "{size}", "--img-num", "1",
    "--buckets", "1,2", "--model-path", "{ckpt}",
    "--warmstart-dir", "{store}"])
engine, batcher, metrics = build_engine(cfg)
rng = np.random.default_rng(0)
engine.start(batcher)
scores = engine.score_batch(
    [rng.random(({size}, {size}, 3), dtype=np.float32) for _ in range(2)])
engine.stop(); batcher.close()
print("RESULT", backend_compile_count(), metrics.warmstart_hits_total.value,
      metrics.warmstart_misses_total.value,
      float(np.asarray(scores).sum()))
"""


@pytest.mark.slow
def test_fresh_interpreter_second_start_pays_zero_backend_compiles(tmp_path):
    """The tentpole contract, end to end: a brand-new process over a
    populated store reaches serving with ZERO XLA backend compiles —
    counted by jax's own compile-event hook, covering the params load
    (skeleton fast path), bucket programs and warm executions alike —
    and scores bit-identically to the cold process that populated it."""
    from deepfake_detection_tpu.models import init_model
    from deepfake_detection_tpu.models.helpers import save_model_checkpoint
    model = create_model(_MODEL, num_classes=2, in_chans=3)
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, _SIZE, _SIZE, 3))
    ckpt = str(tmp_path / "ckpt.msgpack")
    save_model_checkpoint(ckpt, variables)
    store = str(tmp_path / "store")

    def _start():
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", _E2E.format(
                model=_MODEL, size=_SIZE, ckpt=ckpt, store=store)],
            capture_output=True, text=True, timeout=600, env=env)
        assert out.returncode == 0, out.stderr[-4000:]
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("RESULT")][-1]
        _, compiles, hits, misses, total = line.split()
        return int(compiles), int(hits), int(misses), float(total)

    cold_compiles, cold_hits, cold_misses, cold_total = _start()
    assert cold_misses == 2 and cold_hits == 0
    assert cold_compiles > 0
    warm_compiles, warm_hits, warm_misses, warm_total = _start()
    assert warm_compiles == 0, "warm path paid a backend compile"
    assert warm_hits == 2 and warm_misses == 0
    assert warm_total == cold_total                  # bit-identical scores
