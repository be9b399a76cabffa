"""Corpus-scale offline backfill runner: saturation-first scoring.

Where ``runners/serve.py`` optimizes request latency (micro-batch
deadlines, sheds, per-request books) and ``runners/stream.py`` optimizes
stream latency, this runner optimizes ONE thing: clips/s over an
archived corpus.  There is no HTTP, no batcher deadline, and no
per-request bookkeeping in the hot loop — a leased manifest shard
(``deepfake_detection_tpu/backfill``) is driven through a deadline-free
pipeline at full fixed batch:

    mmap/decode (thread pool, overlapped)  →  slab memcpy  →
    uint8 wire + fused normalize inside ONE AOT-compiled program
    (optional ``--stem-s2d`` pixel shuffle folded in)  →
    batch-sharded inference on the unified ('batch','model') mesh  →
    per-shard ``dfd.backfill.verdict.v1`` JSONL

with double-buffered staging (slab k+1 assembles and dispatches while
batch k executes — the DeviceLoader / serving-engine idiom) and zero
steady-state recompiles (one bucket, compiled once, asserted through
the backend-compile probe serving/metrics.py installs).

Resume/books contract: workers lease shards atomically, heartbeat while
scoring, and commit each shard's verdicts with an atomic done marker —
SIGTERM exits 75 at a batch boundary (the train/resilience.py restart
contract) and a relaunch resumes at shard granularity; a dead host's
lease expires by mtime and its partially written shard is re-leased,
torn tail repaired, surviving records kept.  At corpus completion the
books must balance EXACTLY: ``manifest clips == scored + failed +
skipped_dup``, no clip twice, none missing — imbalance is exit 1 with
the discrepancies named, never a summary that rounds them away.

``--dedup`` (packed source only) runs a content-hash pass over the pack
slabs before scoring: a clip whose canonical pixel bytes already occur
earlier in the manifest never enters a device batch — it books a
``skipped_dup`` verdict row pointing at the canonical clip (the same
content addressing the serving verdict cache uses, ``cache/content``).
Archival corpora are full of re-encoded reposts; paying inference per
COPY instead of per CONTENT is the whole point of the cache tier.

Usage::

    python tools/make_lists.py /data/frames --manifest corpus.json \
        --shard-clips 256 [--packed /ssd/pack]
    python -m deepfake_detection_tpu.runners.backfill \
        --manifest corpus.json --data-packed /ssd/pack --out run/ \
        --model-path model.msgpack --batch-size 64
    # more workers = more hosts/processes pointing at the same run dir
"""

from __future__ import annotations

import logging
import os
import queue
import signal
import socket
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

_logger = logging.getLogger(__name__)

__all__ = ["run_backfill", "main", "EXIT_PREEMPTED"]

EXIT_PREEMPTED = 75       # keep in sync with train/resilience.py


class _LeaseLost(RuntimeError):
    """Our shard lease expired and was legitimately stolen while we
    were stalled: the stealer's books win; ours must stop writing."""


def _load_variables(model, cfg, shape):
    """Checkpoint load, mirroring ``runners/serve.py``."""
    import jax

    from ..models import init_model
    from ..models.helpers import load_checkpoint

    variables = init_model(model, jax.random.PRNGKey(0), shape)
    if cfg.model_path and os.path.isdir(cfg.model_path):
        from ..train.checkpoint import load_sharded_for_eval
        variables = load_sharded_for_eval(cfg.model_path, variables)
    elif cfg.model_path:
        variables = load_checkpoint(variables, cfg.model_path,
                                    use_ema=cfg.use_ema, strict=False)
    else:
        _logger.warning("no --model-path: scoring with a seed-0 random "
                        "init (bench/smoke mode)")
    return variables


class _Pipeline:
    """The compiled fixed-bucket score path + its double-buffer state."""

    def __init__(self, cfg, frames: int, hw: Tuple[int, int]):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..params import img_mean, img_std
        from ..parallel.mesh import make_train_mesh
        from ..parallel.sharding import batch_sharding, replicated_sharding

        self.batch = int(cfg.batch_size)
        self.frames = int(frames)
        self.hw = hw
        self.chans = 3 * self.frames
        self.mesh = make_train_mesh()
        dp = self.mesh.shape["batch"]
        if self.batch % dp:
            raise ValueError(
                f"--batch-size {self.batch} does not divide the mesh's "
                f"batch axis ({dp} devices) — the fixed bucket must "
                f"shard evenly")
        self._rep = replicated_sharding(self.mesh)
        self._bsh = batch_sharding(self.mesh)

        from ..models import create_model
        kwargs: Dict[str, Any] = {}
        if cfg.stem_s2d:
            kwargs["stem_s2d"] = True
        model = create_model(cfg.model, num_classes=cfg.num_classes,
                             in_chans=self.chans, **kwargs)
        variables = _load_variables(
            model, cfg, (1, hw[0], hw[1], self.chans))
        self.variables = jax.device_put(variables, self._rep)
        # tiled mean/std ride the call as ARGUMENTS (serving-engine
        # idiom: a constant divisor would strength-reduce to a
        # reciprocal multiply, drifting from the host arithmetic)
        self._mean = jax.device_put(
            jnp.asarray(np.tile(img_mean, self.frames)), self._rep)
        self._std = jax.device_put(
            jnp.asarray(np.tile(img_std, self.frames)), self._rep)

        if cfg.stem_s2d:
            from ..ops.conv import space_to_depth
        else:
            space_to_depth = None

        def _score(variables, x_u8, mean, std):
            x = (x_u8.astype(jnp.float32) - mean) / std
            if space_to_depth is not None:
                x = space_to_depth(x)
            logits = model.apply(variables, x, training=False)
            return jax.nn.softmax(logits, axis=-1)

        t0 = time.monotonic()
        x_spec = jax.ShapeDtypeStruct(
            (self.batch, hw[0], hw[1], self.chans), jnp.dtype(np.uint8))
        # ISSUE 19: the AOT executable store — a hit replaces the whole
        # lower+compile with a deserialize, gated by the golden-batch
        # canary below; ANY unusable entry is a counted loud fallback to
        # the fresh compile, never a crash, never silently wrong
        self.warm_source = "compile"
        self.warm_fallback = ""
        store = fields = manifest = None
        if getattr(cfg, "warmstart_dir", ""):
            from ..serving.warmstart import ExecutableStore, WarmstartMiss
            store = ExecutableStore(cfg.warmstart_dir)
            fields = self._store_fields(cfg, model)
            try:
                compiled, manifest = store.load(
                    fields, execution_devices=list(self.mesh.devices.flat))
                self.warm_source = "store"
            except WarmstartMiss as miss:
                compiled = None
                if miss.reason != "absent":
                    self.warm_fallback = miss.reason
                    _logger.warning(
                        "warm store entry unusable (%s) — falling back "
                        "to fresh compile: %s", miss.reason, miss)
        else:
            compiled = None
        if compiled is not None and \
                not self._canary_ok(compiled, store, fields, manifest):
            compiled = None
            self.warm_source = "compile"
            self.warm_fallback = "canary-reject"
        if compiled is None:
            compiled = jax.jit(
                _score,
                in_shardings=(self._rep, self._bsh, self._rep,
                              self._rep),
                out_shardings=self._rep).lower(
                    self.variables, x_spec, self._mean,
                    self._std).compile()
        self._compiled = compiled
        # warm once: first-run allocation paths + the persistent-cache
        # hit land before the steady-state recompile probe arms
        jax.block_until_ready(self._compiled(
            self.variables,
            jax.device_put(np.zeros((self.batch,) + hw + (self.chans,),
                                    np.uint8), self._bsh),
            self._mean, self._std))
        self.compile_s = time.monotonic() - t0
        if store is not None and self.warm_source == "compile":
            # re-serialize after every miss AND every fallback so the
            # next worker (or the next corrupted-entry recovery) hits
            scores = np.asarray(jax.block_until_ready(self._compiled(
                self.variables, jax.device_put(
                    self._golden_input(), self._bsh),
                self._mean, self._std)))
            if store.save(fields, self._compiled, golden_scores=scores,
                          params_fingerprint=self._fingerprint(),
                          execution_devices=list(self.mesh.devices.flat)):
                _logger.info("warm store: serialized %s", fields["bucket"])

    # ------------------------------------------------------------------
    def _store_fields(self, cfg, model):
        """The complete executable identity (serving-engine idiom):
        program structure + geometry + sharding signature — params
        VALUES stay out (they ride the call as arguments)."""
        import hashlib

        import jax
        import jax.numpy as jnp

        from ..serving import warmkey

        h = hashlib.sha256()
        h.update(repr(model).encode())
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.variables)[0]:
            h.update(jax.tree_util.keystr(path).encode())
            h.update(str(jnp.shape(leaf)).encode())
            h.update(str(jnp.result_type(leaf)).encode())
        import numpy as np
        h.update(np.asarray(self._mean).tobytes())
        h.update(np.asarray(self._std).tobytes())
        return warmkey.key_fields(
            backend=jax.default_backend(),
            device_kind=jax.devices()[0].device_kind,
            program=h.hexdigest(),
            geometry={"hw": list(self.hw), "frames": self.frames,
                      "stem_s2d": bool(cfg.stem_s2d),
                      "model_class": type(model).__name__,
                      "pipeline": "backfill"},
            bucket=self.batch, chans=self.chans, wire="uint8",
            quant="none",
            sharding=repr(sorted(dict(self.mesh.shape).items())))

    def _fingerprint(self) -> str:
        import jax
        import numpy as np

        from ..cache.content import tree_fingerprint
        leaves = jax.tree_util.tree_flatten_with_path(self.variables)[0]
        return tree_fingerprint(
            ((jax.tree_util.keystr(path), np.asarray(leaf))
             for path, leaf in leaves))

    def _golden_input(self):
        import numpy as np
        rng = np.random.default_rng(0xCA9A87)
        return rng.integers(0, 256, (self.batch,) + self.hw
                            + (self.chans,), dtype=np.uint8)

    def _canary_ok(self, compiled, store, fields, manifest) -> bool:
        """Golden-batch gate on a deserialized executable: must execute,
        score finite at the right shape, and — when the manifest was
        stamped by THIS checkpoint — bit-identically to the recorded
        scores.  A fingerprint-skew pass re-stamps the manifest."""
        import jax
        import numpy as np

        from ..serving import warmkey
        try:
            scores = np.asarray(jax.block_until_ready(compiled(
                self.variables,
                jax.device_put(self._golden_input(), self._bsh),
                self._mean, self._std)))
        except Exception as e:                     # noqa: BLE001
            _logger.error("warm store canary: deserialized executable "
                          "failed to run (%s) — recompiling", e)
            return False
        if scores.ndim != 2 or scores.shape[0] != self.batch \
                or not np.all(np.isfinite(scores)):
            _logger.error("warm store canary: bad golden scores "
                          "(shape %s) — recompiling", scores.shape)
            return False
        fp = self._fingerprint()
        if manifest.get("params_fingerprint") == fp:
            ref = warmkey.decode_array(manifest["golden_scores"])
            if ref.shape != scores.shape or \
                    not np.array_equal(ref, scores):
                _logger.error("warm store canary: golden scores drifted "
                              "from the manifest — recompiling")
                return False
        else:
            store.refresh_manifest(fields, golden_scores=scores,
                                   params_fingerprint=fp)
        return True

    def dispatch(self, slab):
        """Async: host→device transfer + compiled call; returns the
        not-yet-materialized device result."""
        import jax
        return self._compiled(
            self.variables, jax.device_put(slab, self._bsh),
            self._mean, self._std)


def _build_dup_map(source, manifest) -> Dict[Tuple[str, int, str], str]:
    """Content-hash pass over the pack: manifest-order duplicate index.

    Hashes every clip's canonical uint8 bytes (``cache/content``'s exact
    addressing — the serving verdict cache's key) straight off the mmap
    slabs, no decode.  The FIRST manifest occurrence of each content
    hash is canonical; every later occurrence maps to its
    ``kind/root/clip`` string.  Manifest order is deterministic, so N
    workers build the identical map independently — no coordination
    file, no races, and a killed+resumed run books the same skips.

    A clip that fails to load is simply absent from the index (it will
    be booked ``ok=false`` by the score path like any damaged clip).
    """
    from ..backfill import manifest_entries
    from ..cache.content import content_hash

    first: Dict[str, Tuple[str, int, str]] = {}
    dup_of: Dict[Tuple[str, int, str], str] = {}
    for entry in manifest_entries(manifest):
        kind, ri, name, _num = entry
        try:
            h = content_hash([source.load(entry)])
        except Exception:                          # noqa: BLE001
            continue
        key = (kind, int(ri), name)
        canon = first.get(h)
        if canon is None:
            first[h] = key
        else:
            dup_of[key] = "/".join(map(str, canon))
    return dup_of


def run_backfill(cfg, stop: Optional[threading.Event] = None
                 ) -> Dict[str, Any]:
    """One worker's pass over the manifest; returns the run summary
    (books, throughput, recompile delta).  ``stop`` (set by the SIGTERM
    handler or a test) stops at the next batch boundary."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from ..backfill import (LeaseDir, ShardVerdictWriter, collect_books,
                            load_manifest, manifest_entries,
                            verify_manifest_source)
    from ..backfill.source import PackSource, TreeSource
    from ..chaos import chaos_from_env
    from ..obs.events import EventLog
    # the probe must observe EVERY compile in this process, including
    # the pipeline's own AOT build — install before any jit
    from ..serving.metrics import (backend_compile_count,
                                   install_backend_compile_listener)

    cfg.validate_required()
    # jax persistent compilation cache: the fallback tier under the AOT
    # executable store — before the first compile
    from ..utils.compile_cache import setup_compile_cache
    setup_compile_cache(cfg.compile_cache_dir)
    install_backend_compile_listener()
    stop = stop if stop is not None else threading.Event()
    chaos = chaos_from_env()
    if chaos.active:
        _logger.warning("DFD_CHAOS active: %s", sorted(chaos.points))

    manifest = load_manifest(cfg.manifest)
    if cfg.data_packed:
        verify_manifest_source(manifest, pack_dir=cfg.data_packed)
        source: Any = PackSource(cfg.data_packed)
        frames = source.frames_per_clip
    else:
        verify_manifest_source(manifest, roots=cfg.data)
        source = TreeSource(cfg.data, frames_per_clip=cfg.frames,
                            image_size=cfg.image_size)
        frames = source.frames_per_clip
    dup_of: Dict[Tuple[str, int, str], str] = {}
    if cfg.dedup:
        t_h = time.monotonic()
        dup_of = _build_dup_map(source, manifest)
        _logger.info(
            "dedup index: hashed %d clips in %.1fs — %d duplicate(s) "
            "will skip the device and book skipped_dup",
            manifest["num_clips"], time.monotonic() - t_h, len(dup_of))
    run_dir = cfg.out
    os.makedirs(run_dir, exist_ok=True)
    owner = cfg.worker_name or f"{socket.gethostname()}-{os.getpid()}"
    lease = LeaseDir(run_dir, owner, ttl_s=cfg.lease_ttl_s)
    # one telemetry stream PER WORKER: N processes share the run dir,
    # and EventLog's open-time torn-tail repair must never truncate a
    # live peer's in-flight write.  tools/obs_report.py merges every
    # telemetry*.jsonl it finds in the dir.
    log = EventLog(os.path.join(run_dir, f"telemetry-{owner}.jsonl"))

    pending = lease.pending_shards(manifest)
    summary: Dict[str, Any] = {
        "worker": owner, "shards_this_proc": 0, "clips_this_proc": 0,
        "failed_this_proc": 0, "skipped_dup_this_proc": 0,
        "lease_lost": 0, "lease_steals": 0,
        "steady_recompiles": 0, "clips_per_s": 0.0, "elapsed_s": 0.0,
        "warmstart_source": "", "warmstart_fallback": "",
    }
    pipe: Optional[_Pipeline] = None
    if pending:
        if source.sample_hw is None:
            # raw tree with no --image-size: the first LOADABLE clip
            # fixes the bucket geometry (every later clip must match,
            # loudly).  A corrupt first clip must not wedge the corpus —
            # it will be booked ok=false like any other failed clip.
            probe_err: Optional[Exception] = None
            for entry in manifest_entries(manifest):
                try:
                    source.load(entry)
                    break
                except Exception as e:             # noqa: BLE001
                    probe_err = e
            if source.sample_hw is None:
                raise RuntimeError(
                    f"no clip in the manifest could be decoded to fix "
                    f"the batch geometry (last error: {probe_err}) — "
                    f"set --image-size explicitly or repair the corpus")
        pipe = _Pipeline(cfg, frames, source.sample_hw)
        summary["warmstart_source"] = pipe.warm_source
        summary["warmstart_fallback"] = pipe.warm_fallback
        _logger.info(
            "bucket %s in %.1fs: batch %d × %dx%d × %dch on mesh "
            "%s; %d/%d shards pending",
            ("deserialized from the warm store"
             if pipe.warm_source == "store" else "compiled"),
            pipe.compile_s, pipe.batch,
            source.sample_hw[1], source.sample_hw[0], pipe.chans,
            dict(pipe.mesh.shape), len(pending), len(manifest["shards"]))
    log.event("run_start", mode="backfill", manifest=cfg.manifest,
              fingerprint=manifest["fingerprint"],
              num_clips=manifest["num_clips"],
              shards_total=len(manifest["shards"]),
              shards_pending=len(pending), worker=owner,
              batch_size=cfg.batch_size,
              mesh_shape=list(pipe.mesh.devices.shape) if pipe else None,
              axis_names=list(pipe.mesh.axis_names) if pipe else None)

    pool = ThreadPoolExecutor(max(1, int(cfg.workers or 0)
                                  or (os.cpu_count() or 4)))
    batch_seq = 0         # device-batch counter (the chaos step)
    acquire_seq = 0       # lease-attempt counter (lease_race chaos step)
    compiles_steady0 = backend_compile_count()
    t_first: Optional[float] = None
    t_last = time.monotonic()

    def _safe_load(entry):
        try:
            return entry, source.load(entry), ""
        except Exception as e:                     # noqa: BLE001
            # a single unreadable clip must cost ONE failed book entry,
            # never the shard (the corpus is archival; damage happens)
            return entry, None, f"{type(e).__name__}: {e}"

    def _process_shard(sid: str) -> bool:
        """Score one leased shard; True iff committed."""
        nonlocal batch_seq, t_first, t_last
        t0 = time.monotonic()
        writer = ShardVerdictWriter(run_dir, sid)
        entries = list(manifest_entries(manifest, sid))
        todo = [e for e in entries
                if (e[0], e[1], e[2]) not in writer.scored_keys]
        resumed = len(entries) - len(todo)
        failed0 = writer.failed       # inherited from a predecessor's
        # surviving records — not this process's doing
        skipped0 = writer.skipped
        if dup_of:
            # book the shard's duplicates up front, before any batch
            # dispatches: the skip rows land in one write, and a kill
            # right after still resumes exactly (scored_keys covers them)
            dups = [e for e in todo if (e[0], e[1], e[2]) in dup_of]
            if dups:
                writer.append_dups(
                    [(kind, ri, name, 0 if kind == "fake" else 1,
                      dup_of[(kind, ri, name)])
                     for kind, ri, name, _num in dups])
                todo = [e for e in todo
                        if (e[0], e[1], e[2]) not in dup_of]
        if resumed:
            _logger.info("%s: resuming a partial shard — %d/%d verdicts "
                         "survive (%d torn bytes dropped)", sid, resumed,
                         len(entries), writer.torn_bytes_dropped)
        B = pipe.batch
        hw, chans = pipe.hw, pipe.chans
        data_wait = device_wait = host_s = 0.0

        q: "queue.Queue" = queue.Queue(maxsize=2)
        shard_stop = threading.Event()     # abandons the producer when
        # the consumer bails early (lost lease, SIGTERM)

        def _halted() -> bool:
            return stop.is_set() or shard_stop.is_set()

        def _put(item) -> bool:
            while not _halted():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        # thread fan-out only pays when a clip is real work (JPEG decode,
        # or a memcpy big enough to release the GIL meaningfully); for
        # small packed clips the per-task scheduling overhead exceeds the
        # mmap read itself
        clip_nbytes = hw[0] * hw[1] * chans
        fan_out = not getattr(source, "zero_decode", False) or \
            clip_nbytes >= (1 << 18)

        def produce():
            for ci in range(0, len(todo), B):
                if _halted():
                    return
                chunk = todo[ci:ci + B]
                loaded = list(pool.map(_safe_load, chunk)) if fan_out \
                    else [_safe_load(e) for e in chunk]
                ok = [(e, a) for e, a, _err in loaded if a is not None]
                fails = [(e, err) for e, a, err in loaded if a is None]
                slab = None
                if ok:
                    # fresh slab every batch: jax CPU device_put
                    # zero-copies aligned host memory, so reuse would
                    # race the still-executing previous batch (the
                    # data/loader.py hazard)
                    slab = np.zeros((B,) + hw + (chans,), np.uint8)
                    for j, (_e, a) in enumerate(ok):
                        slab[j] = a          # the slab memcpy
                if not _put(([e for e, _ in ok], slab, fails)):
                    return
            _put(None)

        producer = threading.Thread(target=produce, daemon=True,
                                    name=f"backfill-produce-{sid}")
        producer.start()

        #: heartbeat/ownership cadence: frequent enough that a live
        #: worker's lease mtime is always far younger than the TTL
        beat_every = min(1.0, cfg.lease_ttl_s / 10.0)
        last_beat = 0.0

        def _confirm_owner() -> None:
            if not lease.still_owner(sid):
                raise _LeaseLost(sid)

        def _beat(now: float) -> None:
            """Heartbeat + ownership on the time cadence.  Runs before
            EVERY write (and in the main loop), so no stall — device,
            data, or cumulative — can exceed ``beat_every`` between an
            ownership confirmation and an append: a TTL-starved worker
            abandons instead of appending duplicates of clips the
            stealer is re-scoring."""
            nonlocal last_beat
            if now - last_beat >= beat_every:
                last_beat = now
                lease.heartbeat(sid)
                _confirm_owner()

        def _complete(staged) -> None:
            nonlocal device_wait, host_s
            ok_entries, fails, out, seq = staged
            t_dev = time.monotonic()
            scores = np.asarray(out) if out is not None else None
            dt = time.monotonic() - t_dev
            device_wait += dt
            _beat(time.monotonic())       # BEFORE the append, always
            t_host = time.monotonic()
            rows = []
            for j, (kind, ri, name, _num) in enumerate(ok_entries):
                s = float(scores[j, 0])                 # P(fake)
                if np.isfinite(s):
                    rows.append((kind, ri, name,
                                 0 if kind == "fake" else 1, s, ""))
                else:
                    # a non-finite score is NEVER served (the serving
                    # engine's contract): book the clip failed instead
                    # of crashing the strict-JSON writer
                    rows.append((kind, ri, name,
                                 0 if kind == "fake" else 1, None,
                                 "NonFiniteScore: model produced a "
                                 "non-finite probability"))
            rows += [(kind, ri, name, 0 if kind == "fake" else 1,
                      None, err)
                     for (kind, ri, name, _num), err in fails]
            writer.append_many(rows)
            host_s += time.monotonic() - t_host
            if chaos.active and chaos.fires("backfill_torn_shard", seq):
                # tear the stream exactly as a mid-write kill would:
                # half a record, no newline, then a hard death that
                # leaves the lease behind (a dead host, not a SIGTERM)
                writer.tear()
                _logger.error("chaos: torn shard %s at batch %d; hard "
                              "exit", sid, seq)
                os._exit(int(chaos.arg("backfill_torn_shard", 137)))

        #: dispatched-but-uncompleted batches, oldest first.  Depth 2 =
        #: batch k+1's transfer AND execution overlap batch k's (two
        #: programs genuinely run concurrently on the CPU backend's
        #: execution pool; on an accelerator this is the classic
        #: stage-ahead queue) while the host appends k-1's verdicts —
        #: the DeviceLoader / serving-engine idiom, one stage deeper.
        inflight: List[Tuple] = []
        committed = False
        lost = False
        try:
            while True:
                t_q = time.monotonic()
                item = None
                while not stop.is_set():
                    try:
                        item = q.get(timeout=0.1)
                        break
                    except queue.Empty:
                        # a data-side stall (slow decode, wedged NFS)
                        # must not let a LIVE worker's lease age into
                        # stealable: keep beating while we wait
                        _beat(time.monotonic())
                        continue
                waited = time.monotonic() - t_q
                data_wait += waited
                if item is None:          # end of shard, or SIGTERM
                    break
                ok_entries, slab, fails = item
                seq = batch_seq
                batch_seq += 1
                if chaos.active and chaos.fires("backfill_kill", seq):
                    # a preemption mid-corpus: deliver a REAL SIGTERM so
                    # the production handler (stop at batch boundary,
                    # release leases, exit 75) is what gets exercised
                    _logger.error("chaos: SIGTERM to self at batch %d",
                                  seq)
                    os.kill(os.getpid(), signal.SIGTERM)
                # dispatch k+1 BEFORE blocking on k-1: transfer + compute
                # overlap the older batches' completion
                out = pipe.dispatch(slab) if slab is not None else None
                if t_first is None:
                    t_first = time.monotonic()
                inflight.append((ok_entries, fails, out, seq))
                if len(inflight) > 2:
                    _complete(inflight.pop(0))
                # liveness + ownership ride the same time cadence during
                # decode-only stretches too (at saturation _beat's two
                # syscalls per cadence are the only ones left in the
                # hot loop)
                _beat(time.monotonic())
            while inflight and not lost:
                _complete(inflight.pop(0))
            t_last = time.monotonic()
            need = {(e[0], e[1], e[2]) for e in entries}
            if not lost and not stop.is_set() and \
                    need <= writer.scored_keys:
                # every manifest clip of this shard has a record (set
                # containment, not a count — an alien record must never
                # mask a missing clip): commit
                book = writer.finalize()
                committed = lease.mark_done(sid, book)
        except _LeaseLost:
            # TTL-starved: another worker legitimately stole the shard —
            # its books win; ours stop here, uncommitted
            _logger.error("%s: lease lost mid-shard (TTL %.0fs too short "
                          "for this batch cadence?); abandoning",
                          sid, cfg.lease_ttl_s)
            summary["lease_lost"] += 1
            lost = True
            t_last = time.monotonic()
        finally:
            shard_stop.set()
            writer.close()
            if not committed:
                lease.release(sid)
        wall = time.monotonic() - t0
        done_clips = writer.records - resumed
        log.metrics(
            shard=sid, clips=len(entries), scored=writer.records -
            writer.failed - writer.skipped, failed=writer.failed,
            skipped_dup=writer.skipped, resumed=resumed,
            committed=committed, wall_s=round(wall, 3),
            clips_per_s=round(done_clips / wall, 2) if wall else None,
            data_wait_s=round(data_wait, 3),
            device_wait_s=round(device_wait, 3),
            host_s=round(host_s, 3),
            backend_compiles=backend_compile_count() - compiles_steady0,
            torn_bytes_dropped=writer.torn_bytes_dropped,
            worker=owner)
        summary["clips_this_proc"] += done_clips
        summary["failed_this_proc"] += writer.failed - failed0
        summary["skipped_dup_this_proc"] += writer.skipped - skipped0
        return committed

    rival: Optional[LeaseDir] = None
    try:
        while not stop.is_set():
            pending = lease.pending_shards(manifest)
            if not pending:
                break
            if cfg.max_shards and \
                    summary["shards_this_proc"] >= cfg.max_shards:
                break
            progressed = False
            for sid in pending:
                if stop.is_set():
                    break
                if cfg.max_shards and \
                        summary["shards_this_proc"] >= cfg.max_shards:
                    break
                if chaos.active and \
                        chaos.fires("backfill_lease_race", acquire_seq):
                    # a rival worker wins the race for THIS shard an
                    # instant before us: our acquire must lose cleanly
                    # and move on; the rival's lease then expires by TTL
                    # and the stale-break path re-leases it
                    if rival is None:
                        rival = LeaseDir(run_dir, "chaos-rival",
                                         ttl_s=cfg.lease_ttl_s)
                    rival.acquire(sid)
                    _logger.error("chaos: rival leased %s ahead of us",
                                  sid)
                acquire_seq += 1
                if not lease.acquire(sid):
                    continue
                if lease.last_steal is not None:
                    summary["lease_steals"] += 1
                    log.event("lease_steal", shard=sid,
                              prev_owner=lease.last_steal.get("owner"))
                    lease.last_steal = None
                if _process_shard(sid):
                    summary["shards_this_proc"] += 1
                    progressed = True
            if not progressed and not stop.is_set() and \
                    lease.pending_shards(manifest):
                # everything left is leased elsewhere (or freshly
                # rivaled): wait out a fraction of the TTL and re-sweep
                stop.wait(min(1.0, cfg.lease_ttl_s / 4.0))
    finally:
        pool.shutdown(wait=False)

    summary["steady_recompiles"] = backend_compile_count() - \
        compiles_steady0
    if t_first is not None:
        summary["elapsed_s"] = round(t_last - t_first, 3)
        if summary["elapsed_s"] > 0:
            summary["clips_per_s"] = round(
                summary["clips_this_proc"] / summary["elapsed_s"], 2)
    books = collect_books(run_dir, manifest)
    summary["books"] = books
    summary["preempted"] = stop.is_set()
    log.event("run_end", **{k: v for k, v in summary.items()})
    log.close()
    if summary["steady_recompiles"]:
        _logger.error("backend compiled %d time(s) AFTER the bucket "
                      "warmup — the zero-recompile contract broke",
                      summary["steady_recompiles"])
    return summary


def main(argv: Optional[Sequence[str]] = None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s: %(message)s")
    from ..config import BackfillConfig
    cfg = BackfillConfig.from_args(argv)

    stop = threading.Event()

    def _sig(signum, frame):
        _logger.info("signal %d: stopping at the next batch boundary",
                     signum)
        stop.set()

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    summary = run_backfill(cfg, stop=stop)
    books = summary["books"]
    _logger.info(
        "worker %s: %d shard(s), %d clip(s) this process at %.1f "
        "clips/s; corpus %d/%d shards done — books: %d manifest == %d "
        "scored + %d failed + %d skipped_dup (%s)", summary["worker"],
        summary["shards_this_proc"], summary["clips_this_proc"],
        summary["clips_per_s"], books["shards_done"],
        books["shards_total"], books["manifest_clips"], books["scored"],
        books["failed"], books["skipped_dup"],
        "BALANCED" if books["balanced"] else
        ("incomplete" if not books["complete"] else "IMBALANCED"))
    if summary["preempted"]:
        return EXIT_PREEMPTED
    if books["complete"] and not books["balanced"]:
        _logger.error("books do not balance: missing=%s duplicated=%s "
                      "alien=%s", books["missing"][:5],
                      books["duplicated"][:5], books["alien"][:5])
        return 1
    if summary["steady_recompiles"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
