"""Backfill subsystem tests (ISSUE 13).

Fast tier: the jax-free contracts — manifest build/validate/staleness,
lease contention and stale-lease expiry (the atomic link/rename CAS),
torn verdict-tail repair + mid-shard resume, done-marker idempotence,
exact books — plus the in-process runner e2e (balanced books, zero
steady-state recompiles, deterministic verdicts, lease-race chaos).

Slow tier (fresh-interpreter subprocess drives, chaos-e2e idiom):
SIGTERM mid-corpus → exit 75 → relaunch resumes at shard granularity
with books exactly balanced and verdicts identical (order-normalized)
to an unkilled run; same for the hard-death + torn-shard point through
the stale-lease path; and the bench --smoke gate.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

pytestmark = pytest.mark.backfill

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from deepfake_detection_tpu.backfill import (                  # noqa: E402
    BackfillManifestStale, LeaseDir, ShardVerdictWriter,
    build_manifest_from_lists, build_manifest_from_pack, collect_books,
    load_manifest, manifest_entries, read_verdicts,
    verify_manifest_source)
from deepfake_detection_tpu.backfill.manifest import save_manifest  # noqa: E402
from deepfake_detection_tpu.backfill.writer import verdict_path  # noqa: E402

EXIT_PREEMPTED = 75


# ---------------------------------------------------------------------------
# corpus builders
# ---------------------------------------------------------------------------

def _write_lists(root, fake=5, real=4, frames=2):
    os.makedirs(root, exist_ok=True)
    for kind, n in (("fake", fake), ("real", real)):
        with open(os.path.join(root, f"{kind}_list.txt"), "w") as f:
            f.write("".join(f"c{c}:{frames}\n" for c in range(n)))


def _write_tree(root, fake=5, real=4, frames=2, size=32, seed=0):
    from PIL import Image
    rng = np.random.default_rng(seed)
    for kind, n in (("fake", fake), ("real", real)):
        for c in range(n):
            d = os.path.join(root, kind, f"c{c}")
            os.makedirs(d, exist_ok=True)
            for i in range(frames):
                Image.fromarray(rng.integers(
                    0, 255, (size, size, 3), dtype=np.uint8)).save(
                    os.path.join(d, f"{i}.jpg"), quality=92)
    _write_lists(root, fake=fake, real=real, frames=frames)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Tiny JPEG tree + packed cache + manifest (module-shared)."""
    from deepfake_detection_tpu.data.packed import write_pack
    td = tmp_path_factory.mktemp("bf_corpus")
    root = str(td / "root")
    _write_tree(root, fake=7, real=6, frames=2, size=32)
    pack = str(td / "pack")
    write_pack(root, pack, image_size=0, frames_per_clip=2,
               shard_size=8, workers=2)
    manifest = build_manifest_from_pack(pack, shard_clips=4)
    mpath = str(td / "manifest.json")
    save_manifest(mpath, manifest)
    return {"root": root, "pack": pack, "manifest_path": mpath,
            "manifest": manifest}


def _cfg(corpus, out, **kw):
    from deepfake_detection_tpu.config import BackfillConfig
    kw.setdefault("model", "vit_tiny_patch16_224")
    kw.setdefault("batch_size", 8)      # conftest mesh = 8 devices
    kw.setdefault("workers", 2)
    return BackfillConfig(manifest=corpus["manifest_path"], out=str(out),
                          data_packed=corpus["pack"], **kw)


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------

class TestManifest:
    def test_from_lists_matches_pack_order(self, tmp_path, corpus):
        m_lists = build_manifest_from_lists(corpus["root"], shard_clips=4)
        m_pack = corpus["manifest"]
        assert [s["clips"] for s in m_lists["shards"]] == \
            [s["clips"] for s in m_pack["shards"]]
        assert m_lists["num_clips"] == 13 and len(m_lists["shards"]) == 4
        # different sources → different fingerprints (lists vs pack)
        assert m_lists["fingerprint"] != m_pack["fingerprint"]

    def test_roundtrip_and_validation(self, tmp_path):
        root = str(tmp_path / "r")
        _write_lists(root, fake=3, real=2)
        m = build_manifest_from_lists(root, shard_clips=2)
        path = str(tmp_path / "m.json")
        save_manifest(path, m)
        assert load_manifest(path) == m
        verify_manifest_source(m, roots=root)
        # structural damage is loud
        bad = dict(m, num_clips=99)
        save_manifest(path, bad)
        with pytest.raises(BackfillManifestStale, match="damaged"):
            load_manifest(path)
        dup = json.loads(json.dumps(m))
        dup["shards"][0]["clips"][0] = dup["shards"][-1]["clips"][-1]
        save_manifest(path, dup)
        with pytest.raises(BackfillManifestStale, match="twice"):
            load_manifest(path)

    def test_source_drift_is_loud(self, tmp_path, corpus):
        root = str(tmp_path / "r")
        _write_lists(root, fake=3, real=2)
        m = build_manifest_from_lists(root, shard_clips=2)
        with open(os.path.join(root, "fake_list.txt"), "a") as f:
            f.write("c99:2\n")
        with pytest.raises(BackfillManifestStale, match="changed"):
            verify_manifest_source(m, roots=root)
        # pack-sourced manifest against a different pack fingerprint
        with pytest.raises(BackfillManifestStale, match="fingerprint"):
            verify_manifest_source(m, pack_dir=corpus["pack"])

    def test_make_lists_cli_emits_manifest(self, tmp_path, corpus):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import make_lists
        out = str(tmp_path / "m.json")
        rc = make_lists.main([corpus["root"], "--manifest", out,
                              "--shard-clips", "5"])
        assert rc == 0
        m = load_manifest(out)
        assert m["num_clips"] == 13 and len(m["shards"]) == 3
        verify_manifest_source(m, roots=corpus["root"])
        # --packed routes the fingerprint to the pack index
        out2 = str(tmp_path / "m2.json")
        rc = make_lists.main([corpus["root"], "--manifest", out2,
                              "--shard-clips", "5", "--packed",
                              corpus["pack"]])
        assert rc == 0
        verify_manifest_source(load_manifest(out2),
                               pack_dir=corpus["pack"])


# ---------------------------------------------------------------------------
# leases
# ---------------------------------------------------------------------------

class TestLease:
    def test_contention_exactly_one_winner(self, tmp_path):
        a = LeaseDir(str(tmp_path), "a", ttl_s=30)
        b = LeaseDir(str(tmp_path), "b", ttl_s=30)
        wins = [a.acquire("s0"), b.acquire("s0")]
        assert sorted(wins) == [False, True]
        # the loser re-leases the NEXT shard instead
        loser = b if wins[0] else a
        assert loser.acquire("s1")

    def test_concurrent_contention(self, tmp_path):
        """Many threads race one shard: exactly one claim succeeds."""
        results = []
        owners = [LeaseDir(str(tmp_path), f"w{i}", ttl_s=30)
                  for i in range(8)]
        barrier = threading.Barrier(8)

        def race(ld):
            barrier.wait()
            results.append(ld.acquire("s0"))

        ts = [threading.Thread(target=race, args=(o,)) for o in owners]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert sum(results) == 1

    def test_stale_lease_expiry_and_steal(self, tmp_path):
        a = LeaseDir(str(tmp_path), "dead-host", ttl_s=5)
        b = LeaseDir(str(tmp_path), "b", ttl_s=5)
        assert a.acquire("s0")
        # a FRESH lease is respected
        assert not b.acquire("s0")
        # ...until its mtime ages past the TTL (a dead host stops
        # heartbeating); then exactly one contender re-leases it
        os.utime(a._lease_path("s0"), (1, 1))
        assert b.acquire("s0")
        assert b.last_steal["owner"] == "dead-host"
        assert b.still_owner("s0") and not a.still_owner("s0")

    def test_heartbeat_keeps_lease_live(self, tmp_path):
        a = LeaseDir(str(tmp_path), "a", ttl_s=1.0)
        b = LeaseDir(str(tmp_path), "b", ttl_s=1.0)
        assert a.acquire("s0")
        time.sleep(0.6)
        a.heartbeat("s0")
        time.sleep(0.6)
        assert not b.acquire("s0")    # beaten 0.6s ago < 1s TTL

    def test_done_marker_idempotent_and_final(self, tmp_path):
        a = LeaseDir(str(tmp_path), "a", ttl_s=30)
        b = LeaseDir(str(tmp_path), "b", ttl_s=30)
        assert a.acquire("s0")
        assert a.mark_done("s0", {"clips": 3})
        assert a.is_done("s0") and b.is_done("s0")
        assert a.done_record("s0")["clips"] == 3
        # done shards are never re-leased, by anyone, ever
        assert not a.acquire("s0") and not b.acquire("s0")
        # marking again is a no-op success
        assert a.mark_done("s0", {"clips": 3})

    def test_lost_lease_refuses_commit(self, tmp_path):
        a = LeaseDir(str(tmp_path), "a", ttl_s=5)
        b = LeaseDir(str(tmp_path), "b", ttl_s=5)
        assert a.acquire("s0")
        os.utime(a._lease_path("s0"), (1, 1))
        assert b.acquire("s0")        # stole it
        # the TTL-starved original must NOT commit over the stealer
        assert not a.mark_done("s0", {"clips": 3})
        assert not a.still_owner("s0")
        assert b.mark_done("s0", {"clips": 3})

    def test_pending_shards(self, tmp_path):
        m = {"shards": [{"id": "s0"}, {"id": "s1"}]}
        a = LeaseDir(str(tmp_path), "a", ttl_s=30)
        assert a.pending_shards(m) == ["s0", "s1"]
        assert a.acquire("s0") and a.mark_done("s0", {})
        assert a.pending_shards(m) == ["s1"]


# ---------------------------------------------------------------------------
# verdict writer + books
# ---------------------------------------------------------------------------

class TestWriter:
    def test_torn_tail_repaired_and_resumed(self, tmp_path):
        run = str(tmp_path)
        w = ShardVerdictWriter(run, "s0")
        w.append_many([("fake", 0, "c0", 0, 0.9, ""),
                       ("fake", 0, "c1", 0, None, "IOError: boom")])
        w.tear()                      # exactly a mid-write kill's damage
        w.close()
        w2 = ShardVerdictWriter(run, "s0")
        assert w2.torn_bytes_dropped > 0
        assert w2.scored_keys == {("fake", 0, "c0"), ("fake", 0, "c1")}
        assert w2.records == 2 and w2.failed == 1
        w2.append("real", 0, "c0", 1, 0.1)
        book = w2.finalize()
        w2.close()
        assert book == {"clips": 3, "scored": 2, "failed": 1,
                        "skipped_dup": 0, "sha256": book["sha256"]}
        # the incremental sha IS the file's content hash
        import hashlib
        with open(verdict_path(run, "s0"), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == book["sha256"]
        # every surviving record is schema-stamped and parseable
        recs = read_verdicts(verdict_path(run, "s0"))
        assert len(recs) == 3
        assert all(r["schema"] == "dfd.backfill.verdict.v1"
                   for r in recs)

    def test_books_name_discrepancies(self, tmp_path):
        run = str(tmp_path)
        root = str(tmp_path / "r")
        _write_lists(root, fake=2, real=1)
        m = build_manifest_from_lists(root, shard_clips=3)
        sid = m["shards"][0]["id"]
        lease = LeaseDir(run, "w", ttl_s=30)
        w = ShardVerdictWriter(run, sid)
        w.append("fake", 0, "c0", 0, 0.9)
        w.append("fake", 0, "c0", 0, 0.9)            # duplicate!
        w.append("fake", 0, "alien", 0, 0.9)         # not in manifest
        w.close()
        assert lease.acquire(sid) and lease.mark_done(sid, {})
        books = collect_books(run, m)
        assert not books["balanced"]
        assert books["duplicated"] == ["fake/0/c0"]
        assert books["alien"] == ["fake/0/alien"]
        assert "real/0/c0" in books["missing"]


# ---------------------------------------------------------------------------
# runner (in-process)
# ---------------------------------------------------------------------------

class TestRunner:
    def test_full_corpus_books_balance_zero_recompiles(self, tmp_path,
                                                       corpus):
        from deepfake_detection_tpu.runners.backfill import run_backfill
        s = run_backfill(_cfg(corpus, tmp_path / "run"))
        assert s["books"]["balanced"], s["books"]
        assert s["steady_recompiles"] == 0
        assert s["clips_this_proc"] == 13
        # relaunch over a finished corpus is a cheap no-op
        s2 = run_backfill(_cfg(corpus, tmp_path / "run"))
        assert s2["shards_this_proc"] == 0
        assert s2["books"]["balanced"]
        # telemetry carries per-shard records + lifecycle events (one
        # stream per worker, named by the lease owner)
        import glob
        tele = glob.glob(str(tmp_path / "run" / "telemetry-*.jsonl"))
        assert len(tele) == 1, tele
        recs = [json.loads(l) for l in open(tele[0])]
        kinds = [r.get("event") or r["type"] for r in recs]
        assert kinds[0] == "run_start" and "run_end" in kinds
        shard_recs = [r for r in recs if r["type"] == "metrics"]
        assert {r["shard"] for r in shard_recs} == \
            {sh["id"] for sh in corpus["manifest"]["shards"]}
        assert all(r["backend_compiles"] == 0 for r in shard_recs)

    def test_dedup_books_skipped_dup_against_manifest(self, tmp_path):
        """--dedup (ISSUE 17): byte-identical clips skip the device and
        book skipped_dup rows naming the canonical clip — books balance
        with the third term, no clip silently absent."""
        import shutil
        from deepfake_detection_tpu.data.packed import write_pack
        from deepfake_detection_tpu.runners.backfill import run_backfill
        root = str(tmp_path / "root")
        _write_tree(root, fake=5, real=4, frames=2, size=32, seed=3)
        # byte-copy three clips: identical JPEG bytes decode to
        # identical pixels, so the pack slabs collide on content hash
        for src, dst in (("fake/c0", "fake/c3"), ("fake/c0", "fake/c4"),
                         ("real/c1", "real/c2")):
            shutil.rmtree(os.path.join(root, dst))
            shutil.copytree(os.path.join(root, src),
                            os.path.join(root, dst))
        pack = str(tmp_path / "pack")
        write_pack(root, pack, image_size=0, frames_per_clip=2,
                   shard_size=8, workers=2)
        manifest = build_manifest_from_pack(pack, shard_clips=4)
        mpath = str(tmp_path / "manifest.json")
        save_manifest(mpath, manifest)
        dup_corpus = {"pack": pack, "manifest_path": mpath,
                      "manifest": manifest}
        run = tmp_path / "run"
        s = run_backfill(_cfg(dup_corpus, run, dedup=True))
        b = s["books"]
        assert b["balanced"], b
        assert b["skipped_dup"] == 3
        assert b["scored"] + b["failed"] + b["skipped_dup"] == \
            b["manifest_clips"] == 9
        assert s["skipped_dup_this_proc"] == 3
        assert s["steady_recompiles"] == 0
        recs = []
        for sh in manifest["shards"]:
            recs += read_verdicts(verdict_path(str(run), sh["id"]))
        skips = [r for r in recs if r.get("skipped_dup")]
        assert len(skips) == 3
        # every skip names a canonical clip that was actually SCORED
        # (never a chain of skips, never a failed clip)
        scored = {f"{r['kind']}/{r['root']}/{r['clip']}"
                  for r in recs if r.get("ok")}
        assert all(r["dup_of"] in scored for r in skips)
        assert all(r["score"] is None and not r["ok"] for r in skips)

    @pytest.mark.slow   # tier-1 budget: a second full corpus run (~3 s)
    # re-proving determinism the slow-tier kill/resume identity drive
    # also pins; the books/zero-recompile runner e2e stays fast
    def test_verdicts_deterministic_across_runs(self, tmp_path, corpus):
        from deepfake_detection_tpu.runners.backfill import run_backfill

        def norm(run_dir):
            recs = []
            for sh in corpus["manifest"]["shards"]:
                recs += read_verdicts(verdict_path(str(run_dir),
                                                   sh["id"]))
            return sorted(json.dumps(r, sort_keys=True) for r in recs)

        run_backfill(_cfg(corpus, tmp_path / "a"))
        run_backfill(_cfg(corpus, tmp_path / "b"))
        assert norm(tmp_path / "a") == norm(tmp_path / "b")
        rec = json.loads(norm(tmp_path / "a")[0])
        assert 0.0 <= rec["score"] <= 1.0 and rec["ok"]

    def test_lease_race_chaos_loses_cleanly_then_steals(self, tmp_path,
                                                        corpus,
                                                        monkeypatch):
        from deepfake_detection_tpu.runners.backfill import run_backfill
        # a rival leases the first shard an instant before us: our
        # acquire must lose, the corpus must still complete (the rival's
        # abandoned lease expires by TTL and is re-leased)
        monkeypatch.setenv("DFD_CHAOS", "backfill_lease_race@0")
        s = run_backfill(_cfg(corpus, tmp_path / "run",
                              lease_ttl_s=1.5))
        assert s["books"]["balanced"], s["books"]
        assert s["lease_steals"] >= 1

    def test_stale_source_refuses_to_run(self, tmp_path, corpus):
        from deepfake_detection_tpu.runners.backfill import run_backfill
        m = json.loads(json.dumps(corpus["manifest"]))
        m["source"]["fingerprint"] = "0" * 64
        m["fingerprint"] = "1" * 64
        mpath = str(tmp_path / "stale.json")
        save_manifest(mpath, m)
        from deepfake_detection_tpu.config import BackfillConfig
        cfg = BackfillConfig(manifest=mpath, out=str(tmp_path / "run"),
                             data_packed=corpus["pack"],
                             model="vit_tiny_patch16_224", batch_size=8)
        with pytest.raises(BackfillManifestStale):
            run_backfill(cfg)

    def test_failed_clips_are_booked_not_fatal(self, tmp_path):
        """Raw-tree source with one undecodable clip: ONE failed book
        entry, the corpus still completes balanced."""
        from deepfake_detection_tpu.runners.backfill import run_backfill
        from deepfake_detection_tpu.config import BackfillConfig
        root = str(tmp_path / "root")
        _write_tree(root, fake=3, real=2, frames=2, size=32)
        m = build_manifest_from_lists(root, shard_clips=3)
        mpath = str(tmp_path / "m.json")
        save_manifest(mpath, m)
        os.remove(os.path.join(root, "fake", "c1", "1.jpg"))
        cfg = BackfillConfig(manifest=mpath, out=str(tmp_path / "run"),
                             data=root, frames=2,
                             model="vit_tiny_patch16_224", batch_size=8,
                             workers=2)
        s = run_backfill(cfg)
        assert s["books"]["balanced"], s["books"]
        assert s["books"]["failed"] == 1
        failed = [r for sh in m["shards"]
                  for r in read_verdicts(
                      verdict_path(str(tmp_path / "run"), sh["id"]))
                  if not r["ok"]]
        assert len(failed) == 1 and failed[0]["clip"] == "c1"
        assert "err" in failed[0] and failed[0]["score"] is None

    def test_nonfinite_scores_booked_failed_not_fatal(self, tmp_path,
                                                      corpus,
                                                      monkeypatch):
        """A model emitting NaN probabilities must cost failed book
        entries (the serving engine's never-serve-NaN contract), not a
        strict-JSON writer crash + relaunch loop."""
        import deepfake_detection_tpu.runners.backfill as bf_mod
        from deepfake_detection_tpu.runners.backfill import run_backfill
        monkeypatch.setattr(
            bf_mod._Pipeline, "dispatch",
            lambda self, slab: np.full((self.batch, 2), np.nan,
                                       np.float32))
        s = run_backfill(_cfg(corpus, tmp_path / "run"))
        assert s["books"]["balanced"], s["books"]
        assert s["books"]["failed"] == corpus["manifest"]["num_clips"]
        assert s["failed_this_proc"] == corpus["manifest"]["num_clips"]
        recs = [r for sh in corpus["manifest"]["shards"]
                for r in read_verdicts(
                    verdict_path(str(tmp_path / "run"), sh["id"]))]
        assert all(not r["ok"] and r["score"] is None and
                   "NonFinite" in r["err"] for r in recs)

    def test_obs_report_renders_backfill_table(self, tmp_path, corpus,
                                               capsys):
        from deepfake_detection_tpu.runners.backfill import run_backfill
        run_backfill(_cfg(corpus, tmp_path / "run"))
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import obs_report
        obs_report.main([str(tmp_path / "run")])
        out = capsys.readouterr().out
        assert "backfill" in out and "BALANCED" in out
        assert "shard-00000" in out and "clips/s" in out


# ---------------------------------------------------------------------------
# fresh-interpreter chaos e2e (slow tier)
# ---------------------------------------------------------------------------

def _spawn_backfill(args, chaos="", timeout=600):
    env = dict(os.environ)
    env.pop("DFD_CHAOS", None)
    if chaos:
        env["DFD_CHAOS"] = chaos
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PYTHONPATH"] = REPO
    import jax
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        jax.config.jax_compilation_cache_dir or "")
    return subprocess.run(
        [sys.executable, "-m", "deepfake_detection_tpu.runners.backfill",
         *args], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout)


@pytest.mark.slow
@pytest.mark.parametrize("fault,expect", [
    ("backfill_kill@1", EXIT_PREEMPTED),      # SIGTERM: graceful stop
    ("backfill_torn_shard@1", 137),           # hard death: torn tail +
])                                            # abandoned lease
def test_kill_midcorpus_resumes_with_exact_books(tmp_path, corpus,
                                                 fault, expect):
    """The acceptance-criterion e2e: a worker dies mid-corpus, the
    relaunch resumes at shard granularity, books balance EXACTLY, and
    the verdict JSONL is identical (order-normalized) to an unkilled
    run's."""
    base = ["--manifest", corpus["manifest_path"],
            "--data-packed", corpus["pack"],
            "--model", "vit_tiny_patch16_224", "--batch-size", "4",
            "--workers", "2", "--lease-ttl-s", "2"]
    out = str(tmp_path / "run")
    r = _spawn_backfill(base + ["--out", out], chaos=fault)
    assert r.returncode == expect, \
        f"rc={r.returncode}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    if expect != EXIT_PREEMPTED:
        # hard death leaves the lease behind; expiry re-leases it
        time.sleep(2.1)
    r2 = _spawn_backfill(base + ["--out", out])
    assert r2.returncode == 0, \
        f"rc={r2.returncode}\n{r2.stdout[-2000:]}\n{r2.stderr[-2000:]}"
    books = collect_books(out, corpus["manifest"])
    assert books["balanced"], books

    ref = str(tmp_path / "ref")
    r3 = _spawn_backfill(base + ["--out", ref])
    assert r3.returncode == 0

    def norm(run_dir):
        recs = []
        for sh in corpus["manifest"]["shards"]:
            recs += read_verdicts(verdict_path(run_dir, sh["id"]))
        return sorted(json.dumps(r, sort_keys=True) for r in recs)

    killed, clean = norm(out), norm(ref)
    assert len(clean) == corpus["manifest"]["num_clips"]
    assert killed == clean


@pytest.mark.slow
def test_chaos_harness_backfill_scenario(tmp_path, corpus):
    """tools/chaos.py's backfill scenario drives the same contract as a
    CLI (the operator runbook path)."""
    import jax
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PYTHONPATH"] = REPO
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        jax.config.jax_compilation_cache_dir or "")
    out = str(tmp_path / "run")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos.py"),
         "backfill", "--fault", "backfill_kill@1", "--",
         sys.executable, "-m", "deepfake_detection_tpu.runners.backfill",
         "--manifest", corpus["manifest_path"],
         "--data-packed", corpus["pack"], "--out", out,
         "--model", "vit_tiny_patch16_224", "--batch-size", "4",
         "--workers", "2", "--lease-ttl-s", "2"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-2000:]}"
    assert "PASS" in r.stdout


@pytest.mark.slow
def test_bench_backfill_smoke(tmp_path):
    """The verify-recipe row: tiny corpus through both pipelines, books
    balanced, zero steady-state recompiles asserted by the bench."""
    import jax
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        jax.config.jax_compilation_cache_dir or "")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_backfill.py"),
         "--smoke"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-2000:]}"
    assert "backfill host-path ceiling" in r.stdout
