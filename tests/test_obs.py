"""Training telemetry subsystem (obs/): renderer parity, JSONL coherence,
endpoint scrapes, the no-new-device-syncs overhead guard, watchdog dump,
strided warp elision."""

import json
import os
import subprocess
import sys
import time
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

pytestmark = pytest.mark.obs

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


# ---------------------------------------------------------------------------
# Shared Prometheus renderer: serving output byte-identical pre/post refactor
# ---------------------------------------------------------------------------

def _old_serving_render(self) -> str:
    """The pre-refactor serving/metrics.py renderer — the golden the
    shared utils/prometheus.py renderer must reproduce byte-for-byte.
    Catalog additions since the refactor (the ISSUE 10 resilience
    counters/gauges) are mirrored here in the same hand-rolled style, so
    the byte-layout lock keeps covering the whole exposition."""
    from deepfake_detection_tpu.serving.metrics import (STAGES,
                                                        backend_compile_count)
    _PREFIX = "dfd_serving"
    lines = []

    def counter(name, help_, value, labels=""):
        lines.append(f"# HELP {_PREFIX}_{name} {help_}")
        lines.append(f"# TYPE {_PREFIX}_{name} counter")
        lines.append(f"{_PREFIX}_{name}{labels} {value}")

    def gauge(name, help_, value):
        lines.append(f"# HELP {_PREFIX}_{name} {help_}")
        lines.append(f"# TYPE {_PREFIX}_{name} gauge")
        lines.append(f"{_PREFIX}_{name} {value}")

    lines.append(f"# HELP {_PREFIX}_requests_total Requests by HTTP "
                 "status")
    lines.append(f"# TYPE {_PREFIX}_requests_total counter")
    with self._requests_lock:
        items = sorted((k, c.value) for k, c in self.requests_total.items())
    for status, value in items:
        lines.append(
            f'{_PREFIX}_requests_total{{status="{status}"}} {value}')
    counter("accepted_total", "Requests offered to the micro-batcher "
            "(books: accepted == cache_hit + scored + shed + deadline "
            "+ failed)", self.accepted_total.value)
    counter("scored_total", "Requests resolved with a score",
            self.scored_total.value)
    counter("failed_total", "Requests resolved with an error (engine "
            "fault, non-finite batch, stall, shutdown)",
            self.failed_total.value)
    counter("shed_total", "Requests rejected 429 (queue full)",
            self.shed_total.value)
    counter("deadline_total", "Requests failed 504 (deadline exceeded)",
            self.deadline_total.value)
    counter("batches_total", "Device batches executed",
            self.batches_total.value)
    counter("batch_rows_total", "Real rows across executed batches",
            self.batch_rows_total.value)
    counter("padded_rows_total", "Padding rows across executed batches",
            self.padded_rows_total.value)
    counter("compiles_total", "Bucket executables built by the engine "
            "(startup warmup only)", self.compiles_total.value)
    counter("backend_compiles_total", "Real XLA backend compiles "
            "observed process-wide (jax monitoring hook; growth after "
            "ready=1 means something recompiled)",
            backend_compile_count())
    counter("reloads_total", "Successful hot weight reloads",
            self.reloads_total.value)
    counter("reload_errors_total", "Rejected/failed hot reloads",
            self.reload_errors_total.value)
    counter("reload_canary_failures_total", "Hot reloads rejected by "
            "the golden-batch canary (non-finite / drifted scores)",
            self.reload_canary_failures_total.value)
    counter("worker_restarts_total", "Engine worker crash recoveries",
            self.worker_restarts_total.value)
    counter("watchdog_recoveries_total", "Watchdog-driven engine "
            "restarts (stuck batch or dead worker)",
            self.watchdog_recoveries_total.value)
    counter("nonfinite_batches_total", "Device batches discarded for "
            "NaN/Inf scores (every row failed 503, never served)",
            self.nonfinite_batches_total.value)
    counter("rewarms_total", "Full AOT bucket re-warm passes after a "
            "recovery (executes existing executables; no recompiles)",
            self.rewarms_total.value)
    counter("breaker_opens_total", "Circuit-breaker closed/half-open "
            "-> open transitions", self.breaker_opens_total.value)
    counter("breaker_probes_total", "Half-open probe requests admitted",
            self.breaker_probes_total.value)
    counter("breaker_rejected_total", "Requests shed 503 by the open "
            "breaker", self.breaker_rejected_total.value)
    # the ISSUE 17 verdict-cache counters, same hand-rolled style
    counter("cache_hit_total", "Requests resolved by the verdict "
            "cache — exact + near-dup + coalesced (books: accepted "
            "== cache_hit + scored + shed + deadline + failed)",
            self.cache_hit_total.value)
    counter("cache_near_hit_total", "Verdict-cache hits via the "
            "near-dup perceptual index (subset of cache_hit_total; "
            "never conflated with exact hits)",
            self.cache_near_hit_total.value)
    counter("cache_coalesced_total", "Requests that rode an "
            "in-flight twin's single dispatch (subset of "
            "cache_hit_total)", self.cache_coalesced_total.value)
    counter("cache_miss_total", "Keyed submits that found no cached "
            "verdict and dispatched", self.cache_miss_total.value)
    counter("cache_insert_total", "Verdicts stored after a scored "
            "miss", self.cache_insert_total.value)
    counter("cache_expired_total", "Verdict-cache entries dropped at "
            "TTL expiry", self.cache_expired_total.value)
    counter("cache_evicted_total", "Verdict-cache entries evicted by "
            "LRU capacity", self.cache_evicted_total.value)
    counter("cache_invalidated_total", "Verdict-cache entries purged "
            "by a reload's fingerprint bump (stale hits are "
            "impossible by construction; this reclaims the memory)",
            self.cache_invalidated_total.value)
    # the ISSUE 19 warm-start store counters, same hand-rolled style
    counter("warmstart_hits_total", "Warm-start store entries "
            "deserialized at warmup (each still gated by the "
            "golden-batch canary before serving)",
            self.warmstart_hits_total.value)
    counter("warmstart_misses_total", "Warm-start store lookups "
            "that found no entry (fresh compile + serialize)",
            self.warmstart_misses_total.value)
    counter("warmstart_fallbacks_total", "Warm-start entries "
            "present but unusable (corrupt/foreign/version-skew) — "
            "counted fallback to fresh compile, never a crash",
            self.warmstart_fallbacks_total.value)
    counter("warmstart_canary_rejects_total", "Deserialized "
            "executables rejected by the golden-batch canary "
            "(non-finite/shape/bit-drift) and recompiled fresh",
            self.warmstart_canary_rejects_total.value)
    counter("warmstart_serialized_total", "Executables serialized "
            "into the warm-start store this process",
            self.warmstart_serialized_total.value)
    # per-model request books (ISSUE 14 multi-model engine)
    from deepfake_detection_tpu.serving.metrics import MODEL_BOOK_KINDS
    with self._model_lock:
        model_items = sorted(
            ((kind, model), c.value)
            for (kind, model), c in self.model_books.items())
    for kind in MODEL_BOOK_KINDS:
        lines.append(f"# HELP {_PREFIX}_model_{kind}_total Per-model "
                     f"request books: {kind}")
        lines.append(f"# TYPE {_PREFIX}_model_{kind}_total counter")
        for (k, model), value in model_items:
            if k == kind:
                lines.append(f'{_PREFIX}_model_{kind}_total'
                             f'{{model="{model}"}} {value}')
    lines.append(f"# HELP {_PREFIX}_bucket_rows_total Rows per executed "
                 "(model, bucket) batch, split real|pad (bench_serve's "
                 "per-bucket padding report)")
    lines.append(f"# TYPE {_PREFIX}_bucket_rows_total counter")
    with self._bucket_lock:
        bucket_items = sorted((k, c.value)
                              for k, c in self.bucket_rows.items())
    for (model, bucket, kind), value in bucket_items:
        lines.append(f'{_PREFIX}_bucket_rows_total{{model="{model}",'
                     f'bucket="{bucket}",kind="{kind}"}} {value}')
    counter("cascade_triaged_total", "Clips scored by the cascade "
            "student (books: triaged == cleared + escalated)",
            self.cascade_triaged_total.value)
    counter("cascade_cleared_total", "Cascade clips resolved by the "
            "student verdict (score outside the suspect band)",
            self.cascade_cleared_total.value)
    counter("cascade_escalated_total", "Cascade clips escalated to "
            "the flagship (books: escalated == flagship_scored + "
            "escalation_failed)", self.cascade_escalated_total.value)
    counter("cascade_flagship_scored_total", "Escalated clips "
            "resolved by a flagship score",
            self.cascade_flagship_scored_total.value)
    counter("cascade_escalation_failed_total", "Escalations that "
            "failed (shed/deadline/engine fault): the student "
            "verdict is served instead — never a silent drop",
            self.cascade_escalation_failed_total.value)
    lines.append(f"# HELP {_PREFIX}_chaos_injections_total Injected "
                 "faults fired (DFD_CHAOS), by point")
    lines.append(f"# TYPE {_PREFIX}_chaos_injections_total counter")
    with self._chaos_lock:
        chaos_items = sorted((k, c.value) for k, c in
                             self.chaos_injections_total.items())
    for point, value in chaos_items:
        lines.append(f'{_PREFIX}_chaos_injections_total'
                     f'{{point="{point}"}} {value}')
    gauge("queue_depth", "Requests waiting in the micro-batch queue",
          self.queue_depth)
    gauge("cache_entries", "Verdicts currently stored in the cache",
          self.cache_entries)
    gauge("inflight", "Requests staged on device", self.inflight)
    gauge("ready", "1 once all buckets are warmed (drops during "
          "recovery re-warm and the reload canary)", int(self.ready))
    gauge("breaker_state", "Circuit breaker state (0 closed, 1 open, "
          "2 half-open)", self.breaker_state)
    gauge("throughput_rps",
          f"Scored requests/sec, trailing {self._window_s:.0f}s window",
          round(self.throughput(), 3))
    from deepfake_detection_tpu.serving.metrics import WARMUP_STAGES
    lines.append(f"# HELP {_PREFIX}_warmup_seconds Cold-start stage "
                 "walls (spawn -> serving), seconds")
    lines.append(f"# TYPE {_PREFIX}_warmup_seconds gauge")
    for stage in WARMUP_STAGES:
        lines.append(f'{_PREFIX}_warmup_seconds{{stage="{stage}"}} '
                     f'{round(self.warmup_seconds[stage], 6)}')
    for stage in STAGES:
        h = self.latency[stage]
        name = f"{_PREFIX}_latency_seconds"
        lines.append(f"# HELP {name} Per-stage request latency")
        lines.append(f"# TYPE {name} histogram")
        counts, s, c = h.snapshot()
        acc = 0
        for bound, n in zip(h.bounds, counts):
            acc += n
            lines.append(f'{name}_bucket{{stage="{stage}",'
                         f'le="{bound!r}"}} {acc}')
        lines.append(
            f'{name}_bucket{{stage="{stage}",le="+Inf"}} {c}')
        lines.append(f'{name}_sum{{stage="{stage}"}} {s}')
        lines.append(f'{name}_count{{stage="{stage}"}} {c}')
    from deepfake_detection_tpu.serving.metrics import CASCADE_TIERS
    for tier in CASCADE_TIERS:
        h = self.cascade_latency[tier]
        name = f"{_PREFIX}_cascade_latency_seconds"
        lines.append(f"# HELP {name} Per-tier cascade latency "
                     "(submit -> verdict)")
        lines.append(f"# TYPE {name} histogram")
        counts, s, c = h.snapshot()
        acc = 0
        for bound, n in zip(h.bounds, counts):
            acc += n
            lines.append(f'{name}_bucket{{tier="{tier}",'
                         f'le="{bound!r}"}} {acc}')
        lines.append(
            f'{name}_bucket{{tier="{tier}",le="+Inf"}} {c}')
        lines.append(f'{name}_sum{{tier="{tier}"}} {s}')
        lines.append(f'{name}_count{{tier="{tier}"}} {c}')
    return "\n".join(lines) + "\n"


def _parse_prom(text):
    """{family: type} and [(name, labels, value)] from an exposition doc."""
    types, samples = {}, []
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# TYPE "):
            _, _, fam, t = line.split(" ", 3)
            types[fam] = t
        elif not line.startswith("#"):
            lhs, value = line.rsplit(" ", 1)
            name, _, labels = lhs.partition("{")
            samples.append((name, "{" + labels if labels else "", value))
    return types, samples


class TestSharedRenderer:
    def _populated(self):
        from deepfake_detection_tpu.serving.metrics import ServingMetrics
        m = ServingMetrics()
        for status in (200, 200, 400, 429, 504):
            m.count_request(status)
        for stage, v in (("queue", 0.0002), ("queue", 0.004),
                         ("preprocess", 0.012), ("device", 0.3),
                         ("total", 31.0)):
            m.latency[stage].observe(v)
        m.shed_total.inc(2)
        m.batches_total.inc(7)
        m.batch_rows_total.inc(19)
        m.padded_rows_total.inc(9)
        m.compiles_total.inc(4)
        m.reloads_total.inc()
        # the ISSUE 14 labeled families: per-model books, per-bucket
        # rows, cascade books + per-tier latency
        m.count_model("accepted", "flagship", 3)
        m.count_model("scored", "flagship", 2)
        m.count_model("scored", "student", 5)
        m.count_bucket_rows("flagship", 4, 3, 1)
        m.count_bucket_rows("student", 16, 12, 4)
        m.cascade_triaged_total.inc(5)
        m.cascade_cleared_total.inc(4)
        m.cascade_escalated_total.inc()
        m.cascade_flagship_scored_total.inc()
        m.cascade_latency["student"].observe(0.003)
        m.cascade_latency["flagship"].observe(0.4)
        # the ISSUE 17 verdict-cache counters + gauge
        m.count_model("cache_hit", "flagship", 2)
        m.cache_hit_total.inc(2)
        m.cache_near_hit_total.inc()
        m.cache_coalesced_total.inc()
        m.cache_miss_total.inc(4)
        m.cache_insert_total.inc(3)
        m.cache_expired_total.inc()
        m.cache_evicted_total.inc()
        m.cache_invalidated_total.inc(2)
        # the ISSUE 19 warm-start counters + stage walls
        m.warmstart_hits_total.inc(2)
        m.warmstart_misses_total.inc()
        m.warmstart_fallbacks_total.inc()
        m.warmstart_canary_rejects_total.inc()
        m.warmstart_serialized_total.inc(2)
        m.warmup_seconds["spawn"] = 0.25
        m.warmup_seconds["import"] = 4.5
        m.warmup_seconds["params_load"] = 1.125
        m.warmup_seconds["compile"] = 30.0625
        m.warmup_seconds["warm"] = 2.5
        m.warmup_seconds["ready"] = 38.4375
        m.cache_entries = 3
        m.queue_depth = 5
        m.inflight = 2
        m.ready = True
        m.count_completion(16, now=time.monotonic())
        return m

    def test_serving_output_byte_identical_pre_post_refactor(self):
        m = self._populated()
        # throughput() is time-dependent: freeze it for the comparison
        m.throughput = lambda now=None: 12.345
        assert m.render_prometheus() == _old_serving_render(m)

    def test_serving_conformance(self):
        m = self._populated()
        types, samples = _parse_prom(m.render_prometheus())
        assert types["dfd_serving_requests_total"] == "counter"
        assert types["dfd_serving_latency_seconds"] == "histogram"
        # every sample belongs to a declared family
        fams = set(types)
        for name, _, _ in samples:
            base = name
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix):
                    base = name[: -len(suffix)]
            assert base in fams, name


def _old_router_render(self) -> str:
    """Hand-rolled mirror of fleet/metrics.py's ``dfd_router_*`` catalog
    (ISSUE 15) — the same byte-layout lock the serving catalog carries:
    the shared renderer must reproduce this exactly, so a scrape-side
    dashboard can never notice a renderer refactor."""
    from deepfake_detection_tpu.fleet.metrics import BOOK_KINDS, STAGES
    del BOOK_KINDS      # documented grouping; the mirror spells names out
    _PREFIX = "dfd_router"
    lines = []

    def counter(name, help_, value):
        lines.append(f"# HELP {_PREFIX}_{name} {help_}")
        lines.append(f"# TYPE {_PREFIX}_{name} counter")
        lines.append(f"{_PREFIX}_{name} {value}")

    def gauge(name, help_, value):
        lines.append(f"# HELP {_PREFIX}_{name} {help_}")
        lines.append(f"# TYPE {_PREFIX}_{name} gauge")
        lines.append(f"{_PREFIX}_{name} {value}")

    lines.append(f"# HELP {_PREFIX}_requests_total Router responses by "
                 "HTTP status")
    lines.append(f"# TYPE {_PREFIX}_requests_total counter")
    with self._requests_lock:
        items = sorted((k, c.value) for k, c in self.requests_total.items())
    for status, value in items:
        lines.append(
            f'{_PREFIX}_requests_total{{status="{status}"}} {value}')
    counter("routed_total", "Requests entering the routing path "
            "(books: routed == cache_hit + forwarded + migrated "
            "+ shed + failed)", self.routed_total.value)
    counter("cache_hit_total", "Requests resolved by the edge "
            "verdict cache (keyed on the fleet weights-epoch; no "
            "replica touched)", self.cache_hit_total.value)
    counter("forwarded_total", "Requests resolved by a replica "
            "response relayed to the client", self.forwarded_total.value)
    counter("migrated_total", "Requests resolved by a migration-"
            "override target (the stream was moved off a drained "
            "replica)", self.migrated_total.value)
    counter("shed_total", "Requests shed at the router (no eligible "
            "replica / every failover attempt shed): 503 + jittered "
            "Retry-After", self.shed_total.value)
    counter("failed_total", "Requests failed on transport errors "
            "after the failover budget (502)", self.failed_total.value)
    counter("retries_total", "Failover attempts past the first "
            "replica (upstream shed, backoff or transport error)",
            self.retries_total.value)
    counter("idle_closed_total", "Connections closed on a header-read "
            "or idle deadline (slowloris/idle hardening, both data "
            "planes)", self.idle_closed_total.value)
    counter("overflow_closed_total", "Connections closed because a "
            "stalled peer let the bounded relay buffer fill",
            self.overflow_closed_total.value)
    counter("upstream_pool_closed_total", "Pooled upstream sockets "
            "closed because their replica retired or went down",
            self.upstream_pool_closed_total.value)
    counter("scrape_errors_total", "Replica health-scrape failures",
            self.scrape_errors_total.value)
    counter("replicas_down_total", "Replica healthy->down "
            "transitions observed by the scraper",
            self.replicas_down_total.value)
    counter("drains_total", "Replica drain operations run",
            self.drains_total.value)
    counter("streams_migrated_total", "Live stream sessions moved to "
            "another replica (snapshot -> restore, books intact)",
            self.streams_migrated_total.value)
    counter("migration_aborts_total", "Stream migrations aborted "
            "(target restore failed; the session was restored back "
            "on its source or dumped to disk — never silently lost)",
            self.migration_aborts_total.value)
    counter("replicas_spawned_total", "Replica children spawned "
            "(launch + autoscaler scale-up)",
            self.replicas_spawned_total.value)
    counter("replicas_retired_total", "Replicas retired cleanly "
            "(drain-first: migrate -> settle -> terminate)",
            self.replicas_retired_total.value)
    counter("replicas_killed_total", "Replica stops that escalated "
            "to SIGKILL (or children that died under the "
            "controller)", self.replicas_killed_total.value)
    counter("autoscale_up_total", "Acted scale-up decisions "
            "(SLO breach held through the hysteresis window)",
            self.autoscale_up_total.value)
    counter("autoscale_down_total", "Acted scale-in decisions "
            "(idle held through the hysteresis window; drain-first)",
            self.autoscale_down_total.value)
    counter("standby_promotions_total", "Scale-ups served by "
            "promoting a parked warm standby into the registry "
            "(ms-scale, no spawn, no compile)",
            self.standby_promotions_total.value)
    counter("backfill_workers_spawned_total", "Backfill tenant "
            "workers launched onto idle capacity",
            self.backfill_workers_spawned_total.value)
    counter("backfill_yields_total", "Backfill tenant workers "
            "yielded at a traffic spike (SIGTERM -> exit-75 lease "
            "release)", self.backfill_yields_total.value)
    lines.append(f"# HELP {_PREFIX}_replica_forwarded_total Requests "
                 "forwarded per replica")
    lines.append(f"# TYPE {_PREFIX}_replica_forwarded_total counter")
    with self._replica_lock:
        rep_items = sorted((k, c.value)
                           for k, c in self.replica_forwarded.items())
    for rid, value in rep_items:
        lines.append(f'{_PREFIX}_replica_forwarded_total'
                     f'{{replica="{rid}"}} {value}')
    gauge("ready", "1 while at least one replica is eligible "
          "(healthy + ready + not draining + not backing off)",
          int(self.ready))
    gauge("replicas", "Registered replicas", self.replicas)
    gauge("healthy_replicas", "Replicas whose scrape succeeds",
          self.healthy_replicas)
    gauge("ready_replicas", "Replicas healthy AND /readyz-ready",
          self.ready_replicas)
    gauge("warming_replicas", "Replicas warming a cold model "
          "(parseable 503 /readyz, or a spawned child inside its "
          "startup grace) — capacity in flight, NOT down",
          self.warming_replicas)
    gauge("draining_replicas", "Replicas draining (no new traffic)",
          self.draining_replicas)
    gauge("autoscale_target_replicas", "The autoscaler's current "
          "desired fleet size (0 while autoscaling is off)",
          self.autoscale_target_replicas)
    gauge("standby_replicas", "Parked fully-warmed standby replicas "
          "(unregistered: hold a capacity slot, invisible to the "
          "ring until promoted)", self.standby_replicas)
    gauge("backfill_workers", "Live backfill tenant workers on "
          "idle capacity", self.backfill_workers)
    for stage in STAGES:
        h = self.latency[stage]
        name = f"{_PREFIX}_latency_seconds"
        lines.append(f"# HELP {name} Router request latency "
                     "(upstream = replica round trip, total = "
                     "socket in -> response out)")
        lines.append(f"# TYPE {name} histogram")
        counts, s, c = h.snapshot()
        acc = 0
        for bound, n in zip(h.bounds, counts):
            acc += n
            lines.append(f'{name}_bucket{{stage="{stage}",'
                         f'le="{bound!r}"}} {acc}')
        lines.append(f'{name}_bucket{{stage="{stage}",le="+Inf"}} {c}')
        lines.append(f'{name}_sum{{stage="{stage}"}} {s}')
        lines.append(f'{name}_count{{stage="{stage}"}} {c}')
    return "\n".join(lines) + "\n"


class TestRouterRenderer:
    def _populated(self):
        from deepfake_detection_tpu.fleet.metrics import RouterMetrics
        m = RouterMetrics()
        for status in (200, 200, 502, 503):
            m.count_request(status)
        m.routed_total.inc(11)    # == 2 + 6 + 1 + 1 + 1 (books exact)
        m.cache_hit_total.inc(2)
        m.forwarded_total.inc(6)
        m.migrated_total.inc()
        m.shed_total.inc()
        m.failed_total.inc()
        m.retries_total.inc(2)
        m.drains_total.inc()
        m.streams_migrated_total.inc(3)
        # replica lifecycle books (ISSUE 18): spawned == retired +
        # killed + still-running (here 3 == 1 + 1 + 1)
        m.replicas_spawned_total.inc(3)
        m.replicas_retired_total.inc()
        m.replicas_killed_total.inc()
        m.autoscale_up_total.inc(2)
        m.autoscale_down_total.inc()
        m.standby_promotions_total.inc()
        m.backfill_workers_spawned_total.inc(2)
        m.backfill_yields_total.inc()
        m.backfill_workers = 1
        m.autoscale_target_replicas = 2
        m.standby_replicas = 1
        m.count_forward("127.0.0.1:8377")
        m.count_forward("127.0.0.1:8379")
        m.latency["upstream"].observe(0.004)
        m.latency["total"].observe(0.006)
        m.ready = True
        m.set_fleet_gauges({"replicas": 2, "healthy": 2, "ready": 2,
                            "warming": 1, "draining": 1, "eligible": 1})
        return m

    def test_router_output_byte_identical_to_mirror(self):
        m = self._populated()
        assert m.render_prometheus() == _old_router_render(m)

    def test_router_conformance(self):
        m = self._populated()
        types, samples = _parse_prom(m.render_prometheus())
        assert types["dfd_router_routed_total"] == "counter"
        assert types["dfd_router_latency_seconds"] == "histogram"
        fams = set(types)
        for name, _, _ in samples:
            base = name
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix):
                    base = name[: -len(suffix)]
            assert base in fams, name


class TestTrainTelemetryRenderer:
    def _telemetry(self, **kw):
        from deepfake_detection_tpu.obs import TrainTelemetry
        return TrainTelemetry(**kw)

    def test_catalog_and_breakdown(self):
        t = self._telemetry(flops_per_sample=1e9, peak_flops=1e12)
        for _ in range(4):
            t.on_step(8, data_wait_s=0.01, step_wall_s=0.05)
        t.on_drain(epoch=1, batch_idx=3, num_updates=4, loss=0.5,
                   prec1=75.0, lr=1e-3, drain_wait_s=0.02,
                   nonfinite_steps=1)
        snap = t.snapshot()
        c, g = snap["counters"], snap["gauges"]
        assert c["steps_total"] == 4 and c["samples_total"] == 32
        assert c["nonfinite_steps_total"] == 1
        assert g["epoch"] == 1 and g["update"] == 4
        assert g["throughput_imgs_per_s"] > 0
        # fractions live in [0, 1] and cover the window
        assert 0 <= g["data_wait_frac"] <= 1
        assert 0 <= g["device_wait_frac"] <= 1
        assert 0 <= g["host_frac"] <= 1
        assert g["data_wait_frac"] + g["device_wait_frac"] + \
            g["host_frac"] <= 1.01
        # mfu = imgs/s * flops * 3 / peak
        assert g["mfu"] == pytest.approx(
            g["throughput_imgs_per_s"] * 1e9 * 3 / 1e12, rel=1e-3)

    @pytest.mark.parametrize("platform,kind,want", [
        ("tpu", "TPU v5 lite", 197e12),     # what a v5e chip reports
        ("cpu", "cpu", 0.0),                # the gauge reads 0 off-TPU
        ("tpu", "TPU v99", None),           # never an assumed peak
    ])
    def test_peak_flops_by_device_kind(self, platform, kind, want):
        from types import SimpleNamespace
        from deepfake_detection_tpu.obs import peak_flops
        dev = SimpleNamespace(platform=platform, device_kind=kind)
        if want is None:
            with pytest.raises(ValueError, match="TPU v99"):
                peak_flops(dev)
        else:
            assert peak_flops(dev) == want

    def test_prometheus_conformance(self):
        t = self._telemetry()
        t.on_step(4, 0.001, 0.01)
        t.on_drain(epoch=0, batch_idx=0, num_updates=1, loss=1.0,
                   prec1=50.0, lr=0.1, drain_wait_s=0.0)
        types, samples = _parse_prom(t.render_prometheus())
        # the full catalog is present even for never-touched families
        for fam in ("dfd_train_steps_total", "dfd_train_rewinds_total",
                    "dfd_train_recovery_snapshots_total",
                    "dfd_train_watchdog_near_misses_total",
                    "dfd_train_mfu", "dfd_train_data_wait_frac",
                    "dfd_train_step_seconds"):
            assert fam in types, fam
        # histogram invariants: cumulative buckets, +Inf == _count
        buckets = [(labels, float(v)) for n, labels, v in samples
                   if n == "dfd_train_step_seconds_bucket"]
        count = next(float(v) for n, _, v in samples
                     if n == "dfd_train_step_seconds_count")
        acc = -1.0
        for labels, v in buckets:
            assert v >= acc, "bucket counts must be cumulative"
            acc = v
        assert buckets[-1][0].endswith('le="+Inf"}') and \
            buckets[-1][1] == count

    def test_collector_names_enter_catalog(self):
        t = self._telemetry()
        t.register_collector(lambda: {"counters": {"input_train_x_total": 3},
                                      "gauges": {"input_train_occ": 0.5}})
        snap = t.snapshot()
        assert snap["counters"]["input_train_x_total"] == 3
        assert snap["gauges"]["input_train_occ"] == 0.5
        assert "dfd_train_input_train_x_total" in t.render_prometheus()

    def test_failing_collector_never_raises(self):
        t = self._telemetry()

        def bad():
            raise RuntimeError("collector exploded")

        t.register_collector(bad)
        assert "dfd_train_up 1" in t.render_prometheus()


# ---------------------------------------------------------------------------
# The step record: one row a step, judged against its neighbours
# ---------------------------------------------------------------------------

def _phases(**kw):
    from deepfake_detection_tpu.obs.telemetry import STEP_PHASES
    assert set(kw) <= set(STEP_PHASES)
    return kw


class TestStepRecord:
    """``on_step`` is a pure function of its inputs: no test here sleeps."""

    P = 0.1

    def _telemetry(self, **kw):
        from deepfake_detection_tpu.obs import TrainTelemetry
        return TrainTelemetry(**kw)

    def _counters(self, t):
        return t.snapshot()["counters"]

    def test_one_slow_step_is_counted_priced_and_filed(self):
        t = self._telemetry()
        base = _phases(prologue_block=0.07, dispatch=0.02)
        for i in range(7):
            t.on_step(1, 0.07, self.P, update=i, batch=i, phases=base)
        c = self._counters(t)
        assert c["steps_total"] == 7 and c["steps_judged_total"] == 0
        for i in range(7, 20):
            t.on_step(1, 0.07, self.P, update=i, batch=i, phases=base)
        c = self._counters(t)
        # the 8th step has 7 before it and judges nothing either
        assert c["steps_judged_total"] == c["normal_steps_total"] == 12
        assert c["normal_step_seconds_total"] == pytest.approx(12 * self.P)
        assert c["slow_steps_total"] == 0
        t.on_step(1, 0.15, 1.8 * self.P, update=20, batch=20,
                  phases=_phases(prologue_block=0.07, dispatch=0.02,
                                 h2d_block=0.8 * self.P))
        c = self._counters(t)
        assert c["steps_judged_total"] == 13 and c["slow_steps_total"] == 1
        assert c["slow_steps_h2d_block_total"] == 1
        assert sum(v for k, v in c.items() if k.startswith("slow_steps_")
                   and k != "slow_steps_total") == 1
        assert c["slow_step_excess_seconds_total"] == \
            pytest.approx(0.8 * self.P)
        assert c["normal_steps_total"] == 12
        assert c["normal_step_seconds_total"] == pytest.approx(12 * self.P)
        assert c["step_dispatch_seconds_total"] == pytest.approx(21 * 0.02)
        assert c["step_prologue_block_seconds_total"] == \
            pytest.approx(21 * 0.07)
        assert c["step_h2d_block_seconds_total"] == pytest.approx(0.8 * self.P)

    @pytest.mark.parametrize("phase", [
        "host_wait", "stage", "h2d_block", "prologue_block", "dispatch",
        "save", "rest"])
    def test_a_slow_step_is_filed_under_the_phase_that_grew(self, phase):
        t = self._telemetry()
        base = dict(host_wait=0.004, stage=0.003, h2d_block=0.001,
                    prologue_block=0.06, dispatch=0.02, save=0.002)
        for i in range(16):
            t.on_step(1, 0.07, self.P, phases=_phases(**base))
        grown = dict(base)
        if phase != "rest":         # rest is the period less the phases
            grown[phase] += 0.05
        t.on_step(1, 0.07, self.P + 0.05, phases=_phases(**grown))
        c = self._counters(t)
        assert c["slow_steps_total"] == 1
        assert c[f"slow_steps_{phase}_total"] == 1

    def test_a_drain_is_not_slow_and_the_steps_after_it_are_short(self):
        """An epoch of a device-bound loop: the first iteration pays the
        drain and nothing else, the next runs ahead into an empty device
        queue, eight wait a step each, the last waits and drains."""
        t = self._telemetry()
        p, d = self.P, 0.25
        for _ in range(6):
            t.on_step(1, 0.0, d, phases=_phases(drain=d))
            t.on_step(1, 0.0, 1e-4, phases=_phases(dispatch=1e-4))
            for _ in range(8):
                t.on_step(1, p, p, phases=_phases(prologue_block=p))
            t.on_step(1, p, p + d, phases=_phases(prologue_block=p, drain=d))
        c = self._counters(t)
        assert c["steps_total"] == 66 and c["steps_judged_total"] == 58
        assert c["slow_steps_total"] == 0
        assert c["slow_step_excess_seconds_total"] == 0
        # 9 of an epoch's 11 steps are normal; the first epoch's first 8
        # steps judge nothing (6 of them would have been normal)
        assert c["normal_steps_total"] == 6 * 9 - 6
        assert c["normal_step_seconds_total"] / c["normal_steps_total"] == \
            pytest.approx(p)

    def test_the_drain_record_carries_one_row_a_step(self, tmp_path):
        from deepfake_detection_tpu.obs import EventLog, read_records
        from deepfake_detection_tpu.obs.telemetry import STEP_FIELDS
        path = str(tmp_path / "telemetry.jsonl")
        t = self._telemetry(event_log=EventLog(path))
        drain = dict(epoch=0, loss=1.0, prec1=50.0, lr=0.1)
        for i, wall in enumerate([0.5, 0.1, 0.3]):
            t.on_step(2, 0.01, wall, update=40 + i, batch=i,
                      phases=_phases(host_wait=0.01, dispatch=0.02,
                                     drain=0.05 if i == 2 else 0.0))
        t.on_drain(batch_idx=2, num_updates=43, drain_wait_s=0.05, **drain)
        t.on_step(2, 0.01, 0.2, update=43, batch=3)
        t.on_drain(batch_idx=3, num_updates=44, drain_wait_s=0.0, **drain)
        t.close()
        first, second = [r for r in read_records(path)
                         if r["type"] == "metrics"]
        assert first["step_fields"] == list(STEP_FIELDS)
        assert len(first["steps"]) == 3 and len(second["steps"]) == 1
        rows = [dict(zip(STEP_FIELDS, r)) for r in first["steps"]]
        assert [r["update"] for r in rows] == [40, 41, 42]
        assert [r["batch"] for r in rows] == [0, 1, 2]
        assert [r["period"] for r in rows] == [500.0, 100.0, 300.0]   # ms
        assert rows[2]["drain"] == 50.0 and rows[0]["drain"] == 0.0
        assert rows[2]["rest"] == pytest.approx(300.0 - 10 - 20 - 50)
        # a caller that hands no phases: all of the period is rest
        assert dict(zip(STEP_FIELDS, second["steps"][0]))["rest"] == 200.0
        # the window's exact p50 / max, from the rows
        g = t.snapshot()["gauges"]
        assert g["step_time_p50_ms"] == g["step_time_max_ms"] == 200.0
        assert first["counters"]["steps_total"] == 3

    def test_window_gauges_are_exact(self):
        t = self._telemetry()
        for wall in (0.102, 0.101, 0.193, 0.103, 0.102):
            t.on_step(3, 0.0, wall)
        t.on_drain(epoch=0, batch_idx=4, num_updates=5, loss=1.0,
                   prec1=50.0, lr=0.1, drain_wait_s=0.0)
        g = t.snapshot()["gauges"]
        assert g["step_time_p50_ms"] == 102.0
        assert g["step_time_max_ms"] == 193.0
        assert g["step_time_ms"] == pytest.approx(120.2)

    def test_the_step_wall_and_the_rows_period_are_kept_apart(self,
                                                              tmp_path):
        """``step_seconds_total`` and the window's mean stay on the step's
        wall time (the accepted shares' denominator, as before the rows);
        the row, the verdicts and the exact gauges take the period, which
        also holds the previous iteration's tail."""
        from deepfake_detection_tpu.obs import EventLog, read_records
        from deepfake_detection_tpu.obs.telemetry import STEP_FIELDS
        path = str(tmp_path / "telemetry.jsonl")
        t = self._telemetry(event_log=EventLog(path))
        for i in range(12):
            t.on_step(2, 0.0, self.P, update=i, batch=i, period=1.2 * self.P,
                      phases=_phases(dispatch=0.01))
        t.on_drain(epoch=0, batch_idx=11, num_updates=12, loss=1.0,
                   prec1=50.0, lr=0.1, drain_wait_s=0.0)
        t.close()
        rec = [r for r in read_records(path) if r["type"] == "metrics"][0]
        c = rec["counters"]
        assert c["step_seconds_total"] == pytest.approx(12 * self.P)
        assert rec["step_ms"] == pytest.approx(100.0)
        assert [dict(zip(STEP_FIELDS, r))["period"] for r in rec["steps"]] \
            == [120.0] * 12
        assert c["steps_judged_total"] == c["normal_steps_total"] == 4
        assert c["normal_step_seconds_total"] == \
            pytest.approx(4 * 1.2 * self.P)
        assert t.snapshot()["gauges"]["step_time_p50_ms"] == 120.0

    def test_only_the_phases_no_other_counter_has_are_summed(self):
        """host_wait, stage and the drain have counters already (``input_*``
        and ``device_wait_seconds_total``); the rows add none beside
        them."""
        from deepfake_detection_tpu.obs.telemetry import STEP_PHASES
        t = self._telemetry()
        t.on_step(1, 0.0, self.P, phases={k: 0.01 for k in STEP_PHASES})
        c = self._counters(t)
        assert sorted(k for k in c if k.startswith("step_")) == [
            "step_dispatch_seconds_total", "step_h2d_block_seconds_total",
            "step_prologue_block_seconds_total", "step_seconds_total"]
        assert c["step_h2d_block_seconds_total"] == 0.01
        assert "dfd_train_step_drain_seconds_total" not in \
            t.render_prometheus()

    def test_a_window_of_any_length_keeps_every_row(self):
        t = self._telemetry()
        for i in range(700):
            t.on_step(1, 0.0, self.P, update=i)
        assert len(t._rows) == 700
        t.on_drain(epoch=0, batch_idx=699, num_updates=700, loss=1.0,
                   prec1=50.0, lr=0.1, drain_wait_s=0.0)
        assert t._rows == []
        t.on_step(1, 0.0, 0.3)
        t.on_drain(epoch=0, batch_idx=700, num_updates=701, loss=1.0,
                   prec1=50.0, lr=0.1, drain_wait_s=0.0)
        assert t.snapshot()["gauges"]["step_time_max_ms"] == 300.0


# ---------------------------------------------------------------------------
# JSONL event log
# ---------------------------------------------------------------------------

class TestEventLog:
    def test_round_trip_schema(self, tmp_path):
        from deepfake_detection_tpu.obs import (SCHEMA_VERSION, EventLog,
                                                read_records)
        p = str(tmp_path / "telemetry.jsonl")
        with EventLog(p) as log:
            log.event("run_start", model="m", epochs=2)
            log.metrics(epoch=0, update=10, imgs_per_s=123.4,
                        counters={"steps_total": 10})
            log.event("epoch_end", epoch=0, train={"loss": 0.5})
        recs = read_records(p)
        assert [r["type"] for r in recs] == ["event", "metrics", "event"]
        assert all(r["v"] == SCHEMA_VERSION for r in recs)
        assert all("t" in r for r in recs)
        assert recs[1]["counters"]["steps_total"] == 10
        # strict JSON (consumable by jq): every line parses with a strict
        # parser and non-finite floats were nulled
        with EventLog(p) as log:
            log.metrics(epoch=0, loss=float("nan"), inf=float("inf"))
        for line in open(p):
            rec = json.loads(line, parse_constant=lambda c: pytest.fail(
                f"non-strict JSON constant {c} in stream"))
        assert rec["loss"] is None and rec["inf"] is None

    def test_torn_tail_repaired_and_append_coherent(self, tmp_path):
        """SIGTERM mid-write → one torn line; the auto-resume relaunch's
        reopen must truncate it so the stream stays coherent (no torn, no
        duplicate records)."""
        from deepfake_detection_tpu.obs import EventLog, read_records
        p = str(tmp_path / "telemetry.jsonl")
        with EventLog(p) as log:
            log.event("run_start")
            log.metrics(epoch=0, update=1)
        with open(p, "a") as f:                 # simulate the torn write
            f.write('{"v":1,"t":123.0,"type":"metrics","update":2,"im')
        log2 = EventLog(p)                      # the relaunch
        assert log2.torn_bytes_dropped > 0
        log2.event("resume", epoch=0, batch=2)
        log2.metrics(epoch=0, update=2)
        log2.close()
        recs = read_records(p)
        assert [r["type"] for r in recs] == \
            ["event", "metrics", "event", "metrics"]
        updates = [r["update"] for r in recs if r["type"] == "metrics"]
        assert updates == [1, 2]                # no torn, no duplicate
        # clean reopen drops nothing
        assert EventLog(p).torn_bytes_dropped == 0

    # (the obs-import-is-jax-free subprocess test moved into dfdlint:
    # DFD001 covers deepfake_detection_tpu.obs / obs.events statically,
    # and tests/test_lint.py's canary imports the whole manifest in one
    # child process)


# ---------------------------------------------------------------------------
# /metrics endpoint e2e
# ---------------------------------------------------------------------------

class TestMetricsEndpoint:
    def test_scrape_and_healthz(self):
        from deepfake_detection_tpu.obs import (TrainTelemetry,
                                                start_metrics_server)
        t = TrainTelemetry()
        t.on_step(8, 0.001, 0.02)
        t.on_drain(epoch=3, batch_idx=5, num_updates=17, loss=0.25,
                   prec1=90.0, lr=1e-4, drain_wait_s=0.001)
        server = start_metrics_server(t, host="127.0.0.1", port=0)
        try:
            base = f"http://127.0.0.1:{server.port}"
            body = urllib.request.urlopen(base + "/metrics",
                                          timeout=10).read().decode()
            types, samples = _parse_prom(body)
            assert types["dfd_train_steps_total"] == "counter"
            assert types["dfd_train_throughput_imgs_per_s"] == "gauge"
            assert types["dfd_train_step_seconds"] == "histogram"
            values = {n: v for n, labels, v in samples if not labels}
            assert float(values["dfd_train_update"]) == 17
            health = urllib.request.urlopen(base + "/healthz",
                                            timeout=10).read().decode()
            assert health.startswith("ok") and "epoch=3" in health
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + "/nope", timeout=10)
        finally:
            server.shutdown()
            server.server_close()


# ---------------------------------------------------------------------------
# Overhead guard: telemetry adds no device syncs to the train loop
# ---------------------------------------------------------------------------

class _ListLoader:
    """Minimal loader: pre-staged host batches, like a DeviceLoader that
    already ran (the overhead guard isolates the LOOP's sync behavior)."""

    def __init__(self, batches):
        self.batches = batches

    def __len__(self):
        return len(self.batches)

    def __iter__(self):
        return iter(self.batches)


def _loop_cfg(**kw):
    base = dict(mixup=0.0, mixup_off_epoch=0, log_interval=2,
                save_images=False, recovery_interval=0, profile=0,
                stem_s2d=False, resolved_in_chans=3)
    base.update(kw)
    return SimpleNamespace(**base)


class TestOverheadGuard:
    def _run_epoch(self, telemetry, devices):
        from deepfake_detection_tpu.losses import cross_entropy
        from deepfake_detection_tpu.models import create_model, init_model
        from deepfake_detection_tpu.optim import create_optimizer
        from deepfake_detection_tpu.train import (create_train_state,
                                                  make_train_step,
                                                  train_one_epoch)
        model = create_model("mnasnet_small", num_classes=2, in_chans=3)
        variables = init_model(model, jax.random.PRNGKey(0), (2, 32, 32, 3),
                               training=True)
        tx = create_optimizer(SimpleNamespace(
            opt="sgd", opt_eps=1e-8, momentum=0.9, weight_decay=0.0,
            lr=1e-3), inject=True)
        state = create_train_state(variables, tx)
        step = make_train_step(model, tx, cross_entropy, mesh=None,
                               bn_mode="global")
        rng = np.random.default_rng(0)
        batches = [(jnp.asarray(rng.normal(size=(4, 32, 32, 3)),
                                jnp.float32),
                    jnp.asarray(np.arange(4) % 2))
                   for _ in range(5)]
        state, metrics = train_one_epoch(
            0, step, state, _ListLoader(batches), _loop_cfg(),
            jax.random.PRNGKey(1), telemetry=telemetry)
        return metrics

    def test_no_new_device_syncs_and_no_array_touches(self, devices,
                                                      monkeypatch):
        """block_until_ready count must be IDENTICAL with telemetry on/off,
        and every value entering the tracker must already be a host float —
        the zero-extra-syncs contract of the tracker."""
        from deepfake_detection_tpu.obs import TrainTelemetry
        calls = {"n": 0}
        real = jax.block_until_ready

        def counting(x):
            calls["n"] += 1
            return real(x)

        monkeypatch.setattr(jax, "block_until_ready", counting)

        calls["n"] = 0
        self._run_epoch(None, devices)
        baseline = calls["n"]

        seen_types = []

        class Checked(TrainTelemetry):
            def on_step(self, n, data_wait_s, step_wall_s, tokens=0, *,
                        update=-1, batch=-1, period=None, phases=None):
                seen_types.extend([type(n), type(data_wait_s),
                                   type(step_wall_s), type(tokens),
                                   type(update), type(batch), type(period)])
                seen_types.extend(type(v) for v in phases.values())
                super().on_step(n, data_wait_s, step_wall_s, tokens,
                                update=update, batch=batch, period=period,
                                phases=phases)

            def on_drain(self, **kw):
                seen_types.extend(type(v) for v in kw.values())
                super().on_drain(**kw)

        t = Checked()
        calls["n"] = 0
        self._run_epoch(t, devices)
        assert calls["n"] == baseline, \
            "telemetry changed the loop's block_until_ready count"
        assert not any(issubclass(tp, jax.Array) for tp in seen_types), \
            "a jax.Array leaked into the telemetry hot path"
        snap = t.snapshot()
        assert snap["counters"]["steps_total"] == 5
        assert snap["counters"]["drains_total"] >= 2

    def test_spans_and_phase_counters_add_no_sync(self, devices, monkeypatch,
                                                  tmp_path):
        """The dfd.* spans cost no device sync, with a profiler session
        open or not; the DeviceLoader's one wait per staged batch after the
        first (its slab-recycle wait) is now awaited in two places of the
        same chain, the batch's copy and then the prologue that reads it
        (a pair, not a new sync); and every per-phase / compile counter is
        a host number."""
        from deepfake_detection_tpu.obs import (TrainTelemetry,
                                                loader_collector, start_trace)
        calls = {"n": 0}
        real = jax.block_until_ready

        waited = []

        def counting(x):
            calls["n"] += 1
            waited.append(x)
            return real(x)

        monkeypatch.setattr(jax, "block_until_ready", counting)
        self._run_epoch(None, devices)
        baseline = calls["n"]
        start_trace(str(tmp_path))
        try:
            calls["n"] = 0
            self._run_epoch(TrainTelemetry(), devices)
            traced = calls["n"]
        finally:
            jax.profiler.stop_trace()
        assert traced == baseline, \
            "an open profiler session changed the loop's sync count"

        loader = _mixup_loader()
        del waited[:]
        yielded = [b[0] for b in loader]
        n = len(yielded)
        assert len(waited) == 2 * (n - 1), \
            "the loader's spans/counters added a block_until_ready"
        # each pair: the uint8 wire batch, then the prologue's output that
        # depends on it — the array the parent's one wait was on (batch
        # k + 1's, awaited before batch k + 2 is pulled)
        for k in range(n - 1):
            put, x = waited[2 * k], waited[2 * k + 1]
            assert put.dtype == jnp.uint8 and put.shape[0] == x.shape[0]
            assert x is yielded[k + 1]
        t = TrainTelemetry()
        t.register_collector(loader_collector(loader))
        for k, v in t.snapshot()["counters"].items():
            assert type(v) in (int, float), (k, type(v))
        loader.close()


# ---------------------------------------------------------------------------
# Watchdog dump file + near-miss counter (satellite)
# ---------------------------------------------------------------------------

class TestWatchdogObservability:
    def test_dump_file_written_on_fire(self, tmp_path):
        from deepfake_detection_tpu.train.resilience import (EXIT_WATCHDOG,
                                                             StallWatchdog)
        dump = str(tmp_path / "watchdog_dump.txt")
        fired = []
        wd = StallWatchdog(0.2, position_fn=lambda: "epoch 9 batch 99",
                           exit_fn=fired.append, first_grace=1.0,
                           dump_path=dump)
        wd.start()
        try:
            deadline = time.monotonic() + 10
            while not fired and time.monotonic() < deadline:
                time.sleep(0.05)
        finally:
            wd.stop()
        assert fired == [EXIT_WATCHDOG]
        text = open(dump).read()
        assert "epoch 9 batch 99" in text
        assert "Thread" in text or "thread" in text   # stack dump present

    def test_near_miss_and_beat_counters(self):
        from deepfake_detection_tpu.train.resilience import StallWatchdog
        wd = StallWatchdog(1.0)
        wd.beat()                    # first beat: no previous age
        assert wd.near_miss_total == 0
        time.sleep(0.6)              # > 0.5 * timeout
        wd.beat()
        assert wd.near_miss_total == 1
        wd.beat()                    # immediate: healthy
        assert wd.near_miss_total == 1
        assert wd.beats_total == 3
        assert wd.beat_age() < 0.5

    def test_from_config_wires_dump_path(self, tmp_path):
        from deepfake_detection_tpu.config import TrainConfig
        from deepfake_detection_tpu.train import Resilience
        cfg = TrainConfig(watchdog_timeout=60.0)
        r = Resilience.from_config(cfg, output_dir=str(tmp_path))
        assert r.watchdog.dump_path == str(tmp_path / "watchdog_dump.txt")


# ---------------------------------------------------------------------------
# Strided warp source (satellite): parity + elision counter
# ---------------------------------------------------------------------------

class TestStridedWarpSource:
    @pytest.fixture(autouse=True)
    def _need_native(self):
        from deepfake_detection_tpu.data import native
        if not native.available():
            pytest.skip("native library unavailable")

    def test_packed_views_warp_copy_free_and_bit_identical(self):
        from deepfake_detection_tpu.data import native
        rng = np.random.default_rng(7)
        base = rng.integers(0, 256, (90, 70, 12), dtype=np.uint8)
        views = [base[..., 3 * i:3 * i + 3] for i in range(4)]
        copies = [np.ascontiguousarray(v) for v in views]
        coeffs = (0.9, -0.08, 4.0, 0.12, 1.05, -2.5)
        before = native.warp_copy_stats()
        out_views = native.warp_affine_batch(views, coeffs, (48, 64),
                                             packed=True)
        mid = native.warp_copy_stats()
        out_copies = native.warp_affine_batch(copies, coeffs, (48, 64),
                                              packed=True)
        after = native.warp_copy_stats()
        np.testing.assert_array_equal(out_views, out_copies)
        # the 4 strided views were elided; contiguous frames pass with
        # neither counter moving (no copy was ever due)
        assert mid["elided"] - before["elided"] == 4
        assert mid["copied"] == before["copied"]
        assert after["elided"] == mid["elided"]
        assert after["copied"] == mid["copied"]

    def test_non_dense_rows_fall_back_to_copy(self):
        """A windowed (cropped) view has non-dense rows — the kernel
        assumption fails, so it must take the staging copy and still be
        bit-identical."""
        from deepfake_detection_tpu.data import native
        rng = np.random.default_rng(3)
        base = rng.integers(0, 256, (90, 70, 12), dtype=np.uint8)
        win = base[5:85, 4:68]
        views = [win[..., 3 * i:3 * i + 3] for i in range(4)]
        copies = [np.ascontiguousarray(v) for v in views]
        coeffs = (1.1, 0.0, -1.0, 0.0, 0.95, 1.5)
        before = native.warp_copy_stats()
        o1 = native.warp_affine_batch(views, coeffs, (40, 52), packed=True)
        after = native.warp_copy_stats()
        o2 = native.warp_affine_batch(copies, coeffs, (40, 52), packed=True)
        np.testing.assert_array_equal(o1, o2)
        assert after["copied"] - before["copied"] == 4

    def test_fused_geometric_on_packed_frames_elides(self):
        """The real hot path: MultiFusedGeometric over PackedFrames-style
        mmap views must hit the strided kernel."""
        from deepfake_detection_tpu.data import native
        from deepfake_detection_tpu.data.transforms import \
            MultiFusedGeometric
        rng = np.random.default_rng(11)
        base = rng.integers(0, 256, (120, 110, 12), dtype=np.uint8)
        views = [base[..., 3 * i:3 * i + 3] for i in range(4)]
        t = MultiFusedGeometric(64, rotate_range=5)
        before = native.warp_copy_stats()
        out = t(views, np.random.default_rng(0))
        after = native.warp_copy_stats()
        assert after["elided"] - before["elided"] == 4
        assert np.asarray(out[0]).shape == (64, 64, 3)


# ---------------------------------------------------------------------------
# Profiler capture + obs_report CLI
# ---------------------------------------------------------------------------

class TestProfileRankGating:
    def test_profile_window_is_rank0_only(self, tmp_path, devices,
                                          monkeypatch):
        """Non-zero ranks must never start_trace into the shared run dir
        (the --profile window's rank-0 gate, regression-pinned)."""
        from deepfake_detection_tpu.losses import cross_entropy
        from deepfake_detection_tpu.models import create_model, init_model
        from deepfake_detection_tpu.optim import create_optimizer
        from deepfake_detection_tpu.train import (create_train_state,
                                                  make_train_step,
                                                  train_one_epoch)
        monkeypatch.setattr(jax, "process_index", lambda: 1)
        model = create_model("mnasnet_small", num_classes=2, in_chans=3)
        variables = init_model(model, jax.random.PRNGKey(0), (2, 32, 32, 3),
                               training=True)
        tx = create_optimizer(SimpleNamespace(
            opt="sgd", opt_eps=1e-8, momentum=0.9, weight_decay=0.0,
            lr=1e-3), inject=True)
        state = create_train_state(variables, tx)
        step = make_train_step(model, tx, cross_entropy, mesh=None,
                               bn_mode="global")
        batches = [(jnp.zeros((2, 32, 32, 3), jnp.float32),
                    jnp.asarray(np.arange(2) % 2)) for _ in range(2)]
        train_one_epoch(0, step, state, _ListLoader(batches),
                        _loop_cfg(profile=2, save_images=False),
                        jax.random.PRNGKey(1), output_dir=str(tmp_path))
        assert not (tmp_path / "profile").exists()

    def test_ondemand_capture_is_rank0_only(self, tmp_path, monkeypatch):
        from deepfake_detection_tpu.obs import ProfilerCapture
        monkeypatch.setattr(jax, "process_index", lambda: 1)
        cap = ProfilerCapture(str(tmp_path), num_steps=1)
        (tmp_path / "PROFILE").touch()
        cap.poll()
        cap.on_step(0)
        assert not cap.active and cap.captures_total == 0
        # the trigger file is left for rank 0 to consume
        assert (tmp_path / "PROFILE").exists()


class TestProfilerCapture:
    def test_file_trigger_bounded_capture(self, tmp_path, devices):
        from deepfake_detection_tpu.obs import ProfilerCapture
        cap = ProfilerCapture(str(tmp_path), num_steps=1)
        trigger = tmp_path / "PROFILE"
        trigger.touch()
        cap.poll()
        x = jnp.ones((4,))
        cap.on_step(10, x)           # starts the window
        assert cap.active
        assert not trigger.exists(), "trigger file must be consumed"
        cap.on_step(11, x)           # 11 >= 10 + 1: stops + writes
        assert not cap.active
        assert cap.captures_total == 1
        trace = tmp_path / "profile" / "ondemand-10"
        assert trace.is_dir()
        assert [p for p in trace.rglob("*") if p.is_file()], \
            "profiler produced no trace files"

    def test_idle_is_cheap_and_inert(self, tmp_path):
        from deepfake_detection_tpu.obs import ProfilerCapture
        cap = ProfilerCapture(str(tmp_path), num_steps=5)
        for i in range(100):
            cap.on_step(i)
        cap.poll()
        assert not cap.active and cap.captures_total == 0


class TestObsReport:
    def test_summarizes_run_dir(self, tmp_path):
        from deepfake_detection_tpu.obs import EventLog
        with EventLog(str(tmp_path / "telemetry.jsonl")) as log:
            log.event("run_start", model="m", mesh_shape=[8, 1],
                      axis_names=["batch", "model"])
            for u in range(1, 4):
                log.metrics(epoch=0, batch=u - 1, update=u,
                            imgs_per_s=100.0 + u, step_ms=10.0,
                            data_wait_frac=0.2, device_wait_frac=0.5,
                            host_frac=0.3, loss=1.0 / u, prec1=50.0,
                            lr=0.1, mfu=0.41,
                            counters={
                                "steps_total": u,
                                "recovery_snapshots_total": 1,
                                "input_train_batches_total": u,
                                "slow_steps_total": 1,
                                "slow_steps_h2d_block_total": 1,
                                "steps_judged_total": 2,
                                "slow_step_excess_seconds_total": 0.08,
                                "input_train_load_seconds_total": 1.5,
                                "input_train_collate_seconds_total": 2.5,
                                "input_train_mixup_seconds_total": 5.0},
                            step_fields=["update", "batch", "period",
                                         "h2d_block", "rest"],
                            steps=[[u - 1, u - 1, 100.0 * u, 80.0 * u,
                                    20.0 * u]])
            log.event("rewind", reason="3 consecutive bad steps")
            log.event("epoch_end", epoch=0, train={"loss": 0.33})
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "obs_report.py"),
             str(tmp_path)],
            capture_output=True, text=True, timeout=120, check=True,
            env=dict(os.environ, PYTHONPATH=_REPO))
        assert "imgs/s" in out.stdout and "ms/step" in out.stdout
        assert "| 0 |" in out.stdout          # the epoch row
        assert "rewind" in out.stdout         # resilience event surfaced
        assert "recovery_snapshots_total = 1" in out.stdout
        assert "host fetch 9.0s = load 1.5s + collate 2.5s + mixup 5.0s" \
            in out.stdout
        # the steps table: p50 / p95 / max of the period and of each phase
        # over the records' rows, and the slow steps by phase
        assert "steps: 3 rows (ms)" in out.stdout
        assert "| period | 200.000 | 300.000 | 300.000 |" in out.stdout
        assert "| h2d_block | 160.000 | 240.000 | 240.000 |" in out.stdout
        assert "slow steps: 1 of 2 judged, 0.080s over their neighbours' " \
            "median (h2d_block 1)" in out.stdout
        # the mesh line (ISSUE 12 satellite): topology from run_start
        assert "mesh: batch=8 × model=1 (8 devices)" in out.stdout
        tail = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "obs_report.py"),
             str(tmp_path), "--tail", "2"],
            capture_output=True, text=True, timeout=120, check=True,
            env=dict(os.environ, PYTHONPATH=_REPO))
        lines = [json.loads(l) for l in tail.stdout.strip().split("\n")]
        assert len(lines) == 2 and lines[-1]["event"] == "epoch_end"


# ---------------------------------------------------------------------------
# Acceptance e2e: SIGTERM kill + auto-resume → ONE coherent JSONL stream
# ---------------------------------------------------------------------------

_CLI_DRIVER = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
if cache:
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from deepfake_detection_tpu.runners.train import launch_main
launch_main(sys.argv[1:])
"""

_E2E_BASE = ["--dataset", "synthetic", "--model", "vit_tiny_patch16_224",
             "--model-version", "", "--input-size-v2", "3,32,32",
             "--batch-size", "2", "--epochs", "2", "--opt", "adamw",
             "--lr", "1e-3", "--sched", "step", "--log-interval", "2",
             "--workers", "1", "--compute-dtype", "float32",
             "--seed", "42", "--recovery-interval", "4"]


def _launch_cli(args, chaos=""):
    env = dict(os.environ)
    env.pop("DFD_CHAOS", None)
    if chaos:
        env["DFD_CHAOS"] = chaos
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        jax.config.jax_compilation_cache_dir or "")
    return subprocess.run([sys.executable, "-c", _CLI_DRIVER, *args],
                          cwd=_REPO, env=env, capture_output=True,
                          text=True, timeout=600)


@pytest.mark.slow
class TestLiveRunScrape:
    """CLI e2e smoke (slow tier, the test_train launch_main precedent —
    fresh-interpreter subprocess runs; the fast tier covers the same
    endpoint semantics in TestMetricsEndpoint)."""

    def test_metrics_port_scrapes_during_live_run(self, tmp_path):
        """--metrics-port serves the full catalog while the run is live."""
        import socket
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        env = dict(os.environ)
        env.pop("DFD_CHAOS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_COMPILATION_CACHE_DIR"] = str(
            jax.config.jax_compilation_cache_dir or "")
        proc = subprocess.Popen(
            [sys.executable, "-c", _CLI_DRIVER, *_E2E_BASE,
             "--experiment", "run", "--metrics-port", str(port),
             "--output", str(tmp_path / "out")],
            cwd=_REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        try:
            body = None
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline and proc.poll() is None:
                try:
                    body = urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=5).read().decode()
                    break
                except OSError:
                    time.sleep(0.25)
            assert proc.poll() is None or proc.returncode == 0, \
                proc.stderr.read()[-2000:]
            assert body is not None, "endpoint never came up"
            types, _ = _parse_prom(body)
            for fam in ("dfd_train_steps_total", "dfd_train_mfu",
                        "dfd_train_rewinds_total",
                        "dfd_train_step_seconds",
                        "dfd_train_input_train_batches_total"):
                assert fam in types, fam
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


@pytest.mark.slow
class TestJsonlAcrossAutoResume:
    """CLI e2e smokes (slow tier): the kill/resume/rewind JSONL coherence
    criterion over REAL fresh-interpreter training runs.  The fast tier
    proves the same torn-tail/append mechanics at unit level
    (TestEventLog.test_torn_tail_repaired_and_append_coherent)."""

    def test_sigterm_kill_resume_single_coherent_stream(self, tmp_path):
        """The acceptance criterion: kill mid-epoch, relaunch with
        --auto-resume — the run dir's telemetry.jsonl must be one strictly
        parseable stream carrying the preempted + resume lifecycle."""
        from deepfake_detection_tpu.obs import read_records
        args = _E2E_BASE + ["--experiment", "run", "--auto-resume",
                            "--output", str(tmp_path / "out")]
        r = _launch_cli(args, chaos="sigterm@11")
        assert r.returncode == 75, \
            f"rc={r.returncode}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
        r2 = _launch_cli(args)
        assert r2.returncode == 0, \
            f"rc={r2.returncode}\n{r2.stdout[-2000:]}\n{r2.stderr[-2000:]}"
        log_path = tmp_path / "out" / "run" / "telemetry.jsonl"
        # every line strictly parseable (no torn, no NaN constants)
        for line in open(log_path):
            json.loads(line, parse_constant=lambda c: pytest.fail(
                f"non-strict constant {c}"))
        recs = read_records(str(log_path))
        events = [r["event"] for r in recs if r["type"] == "event"]
        assert events.count("run_start") == 2      # launch + relaunch
        assert "preempted" in events
        assert "resume" in events
        # run_start records the mesh topology (ISSUE 12 satellite)
        start = next(r for r in recs if r.get("event") == "run_start")
        assert start["mesh_shape"] == [1, 1]       # 1 virtual device
        assert start["axis_names"] == ["batch", "model"]
        assert events[-1] == "run_end"
        # the resume event points at the recovery snapshot's position
        resume = next(r for r in recs if r.get("event") == "resume")
        assert "recovery" in resume["path"]
        # metrics records exist on both sides of the kill and carry the
        # breakdown schema
        metrics = [r for r in recs if r["type"] == "metrics"]
        assert len(metrics) >= 2
        for m in metrics:
            for key in ("imgs_per_s", "step_ms", "data_wait_frac",
                        "device_wait_frac", "host_frac", "counters"):
                assert key in m, key

    def test_rewind_event_recorded(self, tmp_path):
        """A nanbatch burst triggers the guard rewind; the stream must
        carry the rewind event with its reason."""
        from deepfake_detection_tpu.obs import read_records
        args = list(_E2E_BASE)
        args[args.index("--epochs") + 1] = "1"
        r = _launch_cli(args + ["--experiment", "run",
                                "--output", str(tmp_path / "out")],
                        chaos="nanbatch@4x3")
        assert r.returncode == 0, \
            f"rc={r.returncode}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
        recs = read_records(str(tmp_path / "out" / "run" /
                                "telemetry.jsonl"))
        rewinds = [r for r in recs if r.get("event") == "rewind"]
        assert len(rewinds) == 1
        assert "consecutive bad steps" in rewinds[0]["reason"]
        assert "recovery" in rewinds[0]["restored_from"]
        # the window that saw the poisoned steps counted them
        last = [r for r in recs if r["type"] == "metrics"][-1]
        assert last["counters"]["nonfinite_steps_total"] >= 1
        assert last["counters"]["rewinds_total"] == 1


# ---------------------------------------------------------------------------
# Loader stats plumbing
# ---------------------------------------------------------------------------

class TestLoaderStats:
    def test_device_loader_counts_waits(self, devices):
        from deepfake_detection_tpu.data import SyntheticDataset
        from deepfake_detection_tpu.data.loader import create_loader
        from deepfake_detection_tpu.obs import loader_collector
        ds = SyntheticDataset(16, (32, 32, 3), 2, 0)
        loader = create_loader(ds, (3, 32, 32), batch_size=4,
                               is_training=False, num_workers=1,
                               dtype=jnp.float32)
        n = sum(1 for _ in loader)
        assert n == len(loader)
        st = loader.stats
        assert st.batches == n
        assert st.host_wait_s >= 0.0
        out = loader_collector(loader)()
        assert out["counters"]["input_train_batches_total"] == n
        assert out["counters"]["input_train_load_seconds_total"] > 0
        loader.close()


# ---------------------------------------------------------------------------
# Host spans on the profiler's clock + per-phase / compile counters
# ---------------------------------------------------------------------------

class _Forwarding:
    """An attribute-forwarding proxy around the host loader, as the
    benchmark's HostTap is: the program must find ``stats`` through it."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __len__(self):
        return len(self._inner)

    def __iter__(self):
        return iter(self._inner)


def _mixup_loader(wrap=False):
    from deepfake_detection_tpu.data import FastCollateMixup, SyntheticDataset
    from deepfake_detection_tpu.data.loader import create_loader
    loader = create_loader(
        SyntheticDataset(16, (32, 32, 3), 2, 0), (3, 32, 32), batch_size=4,
        is_training=True, num_workers=2, dtype=jnp.float32,
        collate_mixup=FastCollateMixup(0.1, 0.1, 2))
    if wrap:
        loader.loader = _Forwarding(loader.loader)
    return loader


def _mixup_state_and_step():
    """A tiny model's state and its train step for the mixup loader's soft
    targets."""
    from deepfake_detection_tpu.losses import soft_target_cross_entropy
    from deepfake_detection_tpu.models import create_model, init_model
    from deepfake_detection_tpu.optim import create_optimizer
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_train_step)
    model = create_model("mnasnet_small", num_classes=2, in_chans=3)
    variables = init_model(model, jax.random.PRNGKey(0), (2, 32, 32, 3),
                           training=True)
    tx = create_optimizer(SimpleNamespace(
        opt="sgd", opt_eps=1e-8, momentum=0.9, weight_decay=0.0,
        lr=1e-3), inject=True)
    return create_train_state(variables, tx), make_train_step(
        model, tx, soft_target_cross_entropy, mesh=None, bn_mode="global")


def _trace_spans(trace_dir):
    """[(name, {stat: value})] of every dfd.* span in the trace's host
    plane."""
    from jax.profiler import ProfileData
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    assert files, "the profiler wrote no .xplane.pb"
    spans = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("dfd."):
                        spans.append((ev.name, dict(ev.stats)))
    return spans


class TestSpans:
    def test_loader_and_train_loop_spans_reach_the_trace(self, devices,
                                                         tmp_path):
        """One traced epoch of the thread-backend loader under
        train_one_epoch: every dfd.input.* / dfd.train.* span is in the
        profiler's own file with its identifier, and the producer's and the
        consumer's spans of one batch carry the same ``batch``."""
        from deepfake_detection_tpu.obs import start_trace
        from deepfake_detection_tpu.train import train_one_epoch
        state, step = _mixup_state_and_step()
        loader = _mixup_loader()
        cfg = _loop_cfg(recovery_interval=2)
        state, _ = train_one_epoch(0, step, state, loader, cfg,
                                   jax.random.PRNGKey(1))      # compiles
        loader.set_epoch(1)
        start_trace(str(tmp_path))
        try:
            train_one_epoch(1, step, state, loader, cfg,
                            jax.random.PRNGKey(1))
        finally:
            jax.profiler.stop_trace()
        loader.close()
        spans = _trace_spans(tmp_path)
        by_name = {}
        for name, stats in spans:
            by_name.setdefault(name, []).append(stats)
        n = len(loader)
        for name in ("load", "collate", "mixup", "put_wait", "host_wait",
                     "stage", "stage_block", "h2d_block", "prologue_block"):
            got = by_name.get(f"dfd.input.{name}")
            assert got, f"no dfd.input.{name} span in the trace"
            assert all("batch" in st for st in got), (name, got)
        assert all("index" in st for st in by_name["dfd.input.sample"])
        assert len(by_name["dfd.input.sample"]) == n * 4
        # epoch 1 of a 4-batch loader: updates 4..7
        assert sorted(st["step_num"] for st in by_name["dfd.train.step"]) \
            == list(range(n, 2 * n))
        assert by_name.get("dfd.train.drain")
        assert by_name.get("dfd.train.recovery_save")
        produced = {st["batch"] for st in by_name["dfd.input.load"]}
        staged = {st["batch"] for st in by_name["dfd.input.stage"]}
        assert produced == staged == set(range(n))
        # the split waits carry the batch their outer span carries
        outer = sorted(st["batch"] for st in by_name["dfd.input.stage_block"])
        assert outer == list(range(1, n))
        for name in ("h2d_block", "prologue_block"):
            assert sorted(st["batch"] for st in
                          by_name[f"dfd.input.{name}"]) == outer

    @pytest.mark.parametrize("wrap", [False, True],
                             ids=["plain", "proxied"])
    def test_stage_block_is_the_sum_of_its_two_waits(self, devices, wrap):
        """stage_block = h2d_block + prologue_block to float rounding, and
        loader_collector exposes both halves beside the producer's three
        phase counters — also through an attribute-forwarding proxy around
        the host loader (the benchmark's HostTap)."""
        from deepfake_detection_tpu.obs import loader_collector
        loader = _mixup_loader(wrap)
        n = sum(1 for _ in loader)
        st = loader.loader.stats
        assert st.batches == n
        assert st.load_s > 0 and st.collate_s > 0 and st.mixup_s > 0
        dst = loader.stats
        assert dst.h2d_block_s >= 0 and dst.prologue_block_s > 0
        assert dst.h2d_block_s + dst.prologue_block_s == \
            pytest.approx(dst.stage_block_s, rel=1e-9)
        c = loader_collector(loader)()["counters"]
        assert c["input_train_load_seconds_total"] == st.load_s
        assert c["input_train_collate_seconds_total"] == st.collate_s
        assert c["input_train_mixup_seconds_total"] == st.mixup_s
        assert c["input_train_stage_seconds_total"] == dst.stage_s > 0
        assert c["input_train_h2d_block_seconds_total"] == dst.h2d_block_s
        assert c["input_train_prologue_block_seconds_total"] == \
            dst.prologue_block_s
        assert c["input_train_stage_block_seconds_total"] == \
            dst.stage_block_s
        assert "input_train_fetch_seconds_total" not in c
        loader.close()

    def test_the_loop_thread_hands_a_row_a_step(self, devices):
        """train_one_epoch over the real loader: each step's row carries the
        loader's own rises (through ``loader.stats``), the periods tile
        the loop's time and no phase is negative."""
        from deepfake_detection_tpu.obs import TrainTelemetry
        from deepfake_detection_tpu.obs.telemetry import STEP_PHASES
        from deepfake_detection_tpu.train import train_one_epoch
        state, step = _mixup_state_and_step()
        loader = _mixup_loader()
        rows = []

        class Keeping(TrainTelemetry):
            def on_step(self, n, data_wait_s, step_wall_s, tokens=0, *,
                        update=-1, batch=-1, period=None, phases=None):
                assert set(phases) == set(STEP_PHASES)
                rows.append(dict(phases, update=update, batch=batch,
                                 period=period, wall=step_wall_s))
                super().on_step(n, data_wait_s, step_wall_s, tokens,
                                update=update, batch=batch, period=period,
                                phases=phases)

        t = Keeping()
        t0 = time.monotonic()
        for e in range(2):
            loader.set_epoch(e)
            state, _ = train_one_epoch(e, step, state, loader,
                                       _loop_cfg(log_interval=3),
                                       jax.random.PRNGKey(1), telemetry=t)
        wall = time.monotonic() - t0
        n = len(loader)
        assert [r["update"] for r in rows] == list(range(2 * n))
        assert [r["batch"] for r in rows] == list(range(n)) * 2
        assert all(v >= 0 for r in rows for v in r.values())
        st = loader.stats
        for name, total in (("host_wait", st.host_wait_s),
                            ("stage", st.stage_s),
                            ("h2d_block", st.h2d_block_s),
                            ("prologue_block", st.prologue_block_s)):
            assert sum(r[name] for r in rows) == pytest.approx(total)
        assert sum(r["drain"] for r in rows) > 0
        assert all(r["dispatch"] > 0 for r in rows)
        # three phases are summed as their rows are written: over the same
        # steps as step_seconds_total, and at an epoch's end what the
        # loader counted; the drain's seconds are device_wait_seconds_total
        c = t.snapshot()["counters"]
        for name in ("h2d_block", "prologue_block", "dispatch"):
            assert c[f"step_{name}_seconds_total"] == pytest.approx(
                sum(r[name] for r in rows))
        assert c["step_prologue_block_seconds_total"] == pytest.approx(
            st.prologue_block_s)
        assert sum(r["drain"] for r in rows) == pytest.approx(
            c["device_wait_seconds_total"])
        # the phases fit inside their period, the step's wall time (what
        # step_seconds_total sums, as before the rows) ends where the
        # period does and starts later, and the periods tile the epochs
        assert all(sum(r[k] for k in STEP_PHASES) <= r["period"] * 1.001
                   for r in rows)
        assert all(r["wall"] <= r["period"] for r in rows)
        assert c["step_seconds_total"] == pytest.approx(
            sum(r["wall"] for r in rows))
        assert sum(r["period"] for r in rows) <= wall
        loader.close()

    def test_compiles_total_counts_each_program_once(self, devices):
        from deepfake_detection_tpu.obs import TrainTelemetry
        t = TrainTelemetry()
        names = ("compiles_total", "jax_trace_seconds_total",
                 "jax_lower_seconds_total", "backend_compile_seconds_total")

        def read():
            c = t.snapshot()["counters"]
            return [c[k] for k in names]

        f = jax.jit(lambda x: x * 3.0 + 1.0)
        a, b = np.ones((5,), np.float32), np.ones((7,), np.float32)
        c0 = read()
        f(a).block_until_ready()
        c1 = read()
        f(a).block_until_ready()                # same shape: nothing built
        c2 = read()
        f(b).block_until_ready()                # a new shape: one program
        c3 = read()
        assert c1[0] - c0[0] == 1 and c2 == c1 and c3[0] - c2[0] == 1
        assert all(x1 > x0 for x0, x1 in zip(c0[1:], c1[1:]))
        # a second registry reads the same process-wide books
        assert [TrainTelemetry().snapshot()["counters"][k]
                for k in names] == c3
