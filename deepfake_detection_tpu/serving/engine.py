"""Inference engine: multi-model table over a bucketed AOT compile cache,
double-buffered staging, hot weight reload, weight-only PTQ.

Design (mirrors what ``data/loader.py`` does for training input):

* **Model table** — the engine serves N models from ONE worker loop and
  ONE micro-batch queue: each :class:`_ModelEntry` owns its params, its
  canvas geometry, its compiled executables and its reload/canary state.
  The compile cache is keyed ``(model_id, bucket, chans, wire)``; every
  executable is AOT-warmed before the server reports ready, and a model
  added to a warmed engine DROPS readiness until its own warmup ran —
  ``/readyz`` never lies about a cold model.  Requests carry a
  ``model_id`` (HTTP: the ``model`` field / query param, defaulting to
  the primary model) and the request books are mirrored per model.

* **Bucketed compile cache** — the scoring function is AOT-compiled once
  per (model, batch bucket) at startup, *before* the server reports
  ready.  Every device call thereafter hits a pre-compiled executable: a
  partial batch pads up to the nearest bucket and the pad rows are
  sliced off the result.  Because batch rows are independent in eval
  mode (running-stat BN, per-row softmax), the real rows of a padded
  bucket are bit-identical to an unpadded call (tests/test_serving.py).
  Novel shapes cannot recompile silently — an unknown bucket or channel
  width is a hard error, and ``compiles_total`` growing after ready=1 is
  the alarm.

* **uint8 wire** — HTTP threads ship the geometric canvas
  (``params.prepare_canvas``, uint8 HWC); normalize + ×img_num replication
  run inside the compiled call (``params.normalize_replicate`` semantics,
  elementwise float32, bit-identical to the CLI's host version).  Same
  idiom as the training loader's device prologue: 4× less host→device
  traffic and the photometrics get batched for free.

* **Post-training quantization** (serving/quant.py) — ``dtype`` bf16
  casts the params, ``int8`` quantizes conv/dense kernels with
  per-output-channel symmetric scales; the in-trace ``realize_tree``
  dequant fuses into the compiled program next to the normalize
  epilogue.  The transform applies at warmup AND to every hot reload
  from its f32 checkpoint (the canary then gates the *quantized* swap),
  while the shape gate keeps comparing against the f32 template.

* **Double-buffered staging** — while batch k executes, the engine drains
  already-queued requests into batch k+1 and dispatches it (JAX async
  dispatch) before blocking on k's result: transfer/stage of k+1 overlaps
  device compute of k, exactly like ``DeviceLoader.__iter__``.

* **Hot weight reload** — params ride the compiled call as an *argument*
  (not a closure constant), so swapping them is aval-compatible and free
  of recompiles.  A watcher thread per watched model polls a checkpoint
  dir; a new file is loaded host-side through ``models/helpers.py`` and
  swapped in atomically between batches (the A/B path).  Shape-
  incompatible checkpoints — including a checkpoint of a DIFFERENT
  model's tree — are rejected loudly, counted, and the old weights keep
  serving.

* **Crash recovery** — an exception anywhere in the serve loop fails the
  affected requests (HTTP 500) and restarts the loop; the worker thread
  never dies with requests stranded.

* **Self-healing** (serving/resilience.py) — the failure modes crash
  recovery can't absorb have their own recovery contracts, each loudly
  counted in /metrics and each reachable through an env-gated
  ``DFD_CHAOS`` injection point (``serve_exc`` / ``serve_nan`` /
  ``serve_hang`` / ``serve_kill`` / ``torn_reload``, stepped by device
  batch or reload attempt — ``chaos.py``'s fire-once grammar):

  - a batch that returns **NaN/Inf scores** fails every rider with 503
    (``nonfinite_batches_total``) — a non-finite score is never served;
  - a batch that **never completes** (or a worker that died outright)
    trips the stuck-batch watchdog: in-flight requests fail 503,
    readiness DROPS, a new worker generation starts, and every AOT
    bucket of every model is re-executed (no recompiles — the
    executables survive) before ``/readyz`` goes true again;
  - **consecutive batch failures** open a circuit breaker (immediate
    503 + Retry-After at the HTTP edge, half-open probe after the
    cooldown, close on probe success);
  - a **hot reload** must pass a golden-batch canary (finite,
    shape-correct, optionally drift-bounded scores — run on the
    QUANTIZED candidate under the target's serving dtype) before the
    swap; torn/garbage/mismatched checkpoints are rejected loudly and
    the old weights keep serving bit-identically.
"""

from __future__ import annotations

import hashlib
import logging
import os
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..cache.content import tree_fingerprint
from ..chaos import chaos_from_env
from ..params import image_max_height, img_mean, img_num as _default_img_num, \
    img_std
from .batcher import MicroBatcher, Request, pick_bucket
from .metrics import ServingMetrics
from .quant import canonical_mode, quant_summary, quantize_tree, realize_tree
from .resilience import (CircuitBreaker, EngineStalled, NonFiniteScores,
                         ServeWatchdog, torn_copy)

_logger = logging.getLogger(__name__)

__all__ = ["InferenceEngine", "DEFAULT_BUCKETS"]

DEFAULT_BUCKETS = (1, 4, 16, 64)

#: checkpoint filenames the reload watcher considers (others — .tmp
#: renames in flight, logs — are ignored)
_CKPT_SUFFIXES = (".msgpack", ".ckpt", ".flax", ".pkt")


def _params_fingerprint(host_tree: Any, dtype: str) -> str:
    """Stable hex digest of a host-side params tree: the weight identity
    the verdict cache keys on (ISSUE 17) and ``/readyz`` exposes.

    Digests every leaf's key-path, shape, dtype and bytes, plus the
    serving dtype — an f32→bf16/int8 swap of the SAME checkpoint scores
    differently and must never share cached verdicts."""
    leaves = jax.tree_util.tree_flatten_with_path(host_tree)[0]
    return tree_fingerprint(
        ((jax.tree_util.keystr(path), np.asarray(leaf))
         for path, leaf in leaves),
        extra=(canonical_mode(dtype),))


class _ModelEntry:
    """One served model: params, geometry, compiled programs, reload and
    canary state.  The engine's model table maps ``model_id`` → entry."""

    __slots__ = ("model_id", "model", "image_size", "img_num", "dtype",
                 "multi_frame", "host_template", "var_shapes", "variables",
                 "mean", "std", "mean_multi", "std_multi", "compiled",
                 "golden", "golden_ref", "fingerprint", "reload_count",
                 "last_reload_key", "reload_attempts", "watcher", "warmed")

    def __init__(self, model_id: str, model, variables, *,
                 image_size: int, img_num: int, dtype: str,
                 wire: str, multi_frame: bool):
        self.model_id = model_id
        self.model = model
        self.image_size = int(image_size)
        self.img_num = int(img_num)
        self.dtype = canonical_mode(dtype)
        # multi-frame needs a second program per bucket only on the uint8
        # wire (float32 payloads share the (·, ·, 3·img_num) shape)
        self.multi_frame = bool(multi_frame) and wire == "uint8" \
            and self.img_num > 1
        # host-side f32 template: the reload merge target AND the shape
        # gate — reloads stay f32 on disk regardless of serving dtype
        self.host_template = jax.tree.map(np.asarray, variables)
        self.var_shapes = jax.tree.map(
            lambda a: (tuple(np.shape(a)), np.asarray(a).dtype),
            self.host_template)
        # the device copy is what executes: PTQ applies here (and to
        # every reload), never to the template
        self.variables = jax.device_put(quantize_tree(variables,
                                                      self.dtype))
        # device_put of host arrays is a pure transfer: the warm path
        # must not pay (or count) a single backend compile for constants
        self.mean = jax.device_put(np.asarray(img_mean, np.float32))
        self.std = jax.device_put(np.asarray(img_std, np.float32))
        # multi-frame wire: mean/std tiled to the 3·img_num clip channels
        # so the SAME per-element arithmetic runs whether the channels
        # came from replication or img_num distinct frames
        self.mean_multi = jax.device_put(
            np.tile(np.asarray(img_mean, np.float32), self.img_num))
        self.std_multi = jax.device_put(
            np.tile(np.asarray(img_std, np.float32), self.img_num))
        self.compiled: Dict[Tuple[int, int], Any] = {}  # (bucket, chans)
        self.golden: Optional[np.ndarray] = None
        self.golden_ref: Optional[np.ndarray] = None
        # weight identity: part of every verdict-cache key, so a reload
        # (which re-assigns this atomically under the commit lock) orphans
        # all cached verdicts of the old weights by construction
        self.fingerprint = _params_fingerprint(self.host_template,
                                               self.dtype)
        self.reload_count = 0
        self.last_reload_key: Optional[Tuple[str, float, int]] = None
        self.reload_attempts = 0           # torn_reload chaos step counter
        self.watcher: Optional[threading.Thread] = None
        self.warmed = False


class _Staged:
    __slots__ = ("requests", "out", "bucket", "dispatch_t", "seq",
                 "model_id")

    def __init__(self, requests: List[Request], out: Any, bucket: int,
                 dispatch_t: float, seq: int, model_id: str):
        self.requests = requests
        self.out = out
        self.bucket = bucket
        self.dispatch_t = dispatch_t
        self.seq = seq          # device-batch sequence (the chaos step)
        self.model_id = model_id


class InferenceEngine:
    def __init__(self, model, variables, *,
                 image_size: int = image_max_height,
                 img_num: int = _default_img_num,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 metrics: Optional[ServingMetrics] = None,
                 wire: str = "float32",
                 multi_frame: bool = True,
                 warmup: bool = True,
                 dtype: str = "f32",
                 model_id: str = "default",
                 watchdog_timeout_s: float = 30.0,
                 breaker_threshold: int = 5,
                 breaker_open_s: float = 5.0,
                 reload_drift_tol: float = -1.0,
                 retry_jitter_s: float = 2.0,
                 warmstart=None,
                 warm_priority: Optional[Sequence[int]] = None,
                 warm_parallel: int = 0,
                 chaos=None):
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"invalid buckets {buckets}")
        #: warm-start executable store (serving/warmstart.py) or None —
        #: warmup consults it before paying lower().compile()
        self.warmstart = warmstart
        self._warm_priority = tuple(int(b) for b in (warm_priority or ()))
        bad = [b for b in self._warm_priority if b not in self.buckets]
        if bad:
            raise ValueError(
                f"warm_priority {bad} not in buckets {self.buckets}")
        self._warm_parallel = int(warm_parallel)
        #: readiness phase: cold -> degraded (staged warmup: priority
        #: bucket serving, rest warming in background) -> ready
        self._phase = "cold"
        self._warm_thread: Optional[threading.Thread] = None
        #: per-unit compile walls + last warmup wall (the staged-warmup
        #: overlap test reads these; keys are (bucket, chans))
        self.warm_compile_walls: Dict[Tuple[int, int], float] = {}
        self.last_warmup_wall = 0.0
        if wire not in ("float32", "uint8"):
            raise ValueError(f"wire must be float32|uint8, got {wire!r}")
        self.wire = wire
        self._multi_frame_opt = bool(multi_frame)
        self.metrics = metrics if metrics is not None else ServingMetrics()
        # real-compile observer: a silent recompile anywhere in the process
        # shows up in /metrics as backend_compiles_total growth (the
        # engine's own counter below only counts its AOT bucket builds)
        from .metrics import install_backend_compile_listener
        install_backend_compile_listener()
        # the model table; insertion order is stable, the FIRST entry is
        # the primary (default-routed) model
        self._models: Dict[str, _ModelEntry] = {}
        self.default_model_id = str(model_id)
        #: authoritative in-flight ledger — staged sub-batches live here
        #: from dispatch until completion, so the stuck-batch watchdog
        #: can read the oldest dispatch time even while the worker is
        #: blocked inside a completion
        self._pending: List[_Staged] = []
        self._pending_lock = threading.Lock()
        # reload box: latest submitted host tree per model id
        self._reload_box: Dict[str, Tuple[Any, str]] = {}
        self._reload_lock = threading.Lock()
        self._stop = threading.Event()
        self._worker: Optional[threading.Thread] = None
        self._watcher: Optional[threading.Thread] = None   # primary's
        self._batcher: Optional[MicroBatcher] = None
        # resilience: chaos injector, worker generations, breaker, watchdog
        self.chaos = chaos if chaos is not None else chaos_from_env()
        self._gen = 0                      # bumped by every recovery; a
        # stale worker checks it before touching shared state
        self._batch_seq = 0                # device-batch counter (chaos step)
        self._recover_lock = threading.Lock()
        self.reload_drift_tol = float(reload_drift_tol)
        self.breaker = CircuitBreaker(breaker_threshold, breaker_open_s,
                                      metrics=self.metrics,
                                      retry_jitter_s=retry_jitter_s)
        self.watchdog = ServeWatchdog(
            watchdog_timeout_s, self._oldest_dispatch, self._worker_alive,
            self._recover)
        # a recovery re-warm against a TRULY hung device would block the
        # watchdog thread forever in block_until_ready — run it bounded
        self._rewarm_timeout_s = max(30.0, 4.0 * float(watchdog_timeout_s))
        self._rewarm_thread: Optional[threading.Thread] = None
        self._canary_hook = None           # test seam: runs mid-canary
        #: verdict cache (cache/store.py VerdictCache), attached by the
        #: runner; start() hands it (plus the fingerprint resolver) to
        #: the batcher, and a reload commit purges the orphaned entries
        self.verdict_cache = None
        #: on-demand trace capture (obs/profiler.py ProfilerCapture),
        #: attached by the runner: the worker reports its device-batch
        #: counter once per loop and polls the trigger file while idle
        self.profiler = None

        self.add_model(self.default_model_id, model, variables,
                       image_size=image_size, img_num=img_num, dtype=dtype)
        if warmup:
            self.warmup()

    # ------------------------------------------------------------------
    # model table
    # ------------------------------------------------------------------
    def add_model(self, model_id: str, model, variables, *,
                  image_size: Optional[int] = None,
                  img_num: Optional[int] = None,
                  dtype: str = "f32") -> None:
        """Register one more model in the table.  Readiness DROPS until
        :meth:`warmup` has AOT-compiled + warmed the new entry's buckets
        — a cold model must never be routable behind a ready /readyz."""
        model_id = str(model_id)
        # table mutation rides the recovery lock: the watchdog's
        # recovery (and its re-warm probe) iterates this dict from
        # another thread
        with self._recover_lock:
            if model_id in self._models:
                raise ValueError(
                    f"model id {model_id!r} already registered")
            primary = next(iter(self._models.values()), None)
            entry = _ModelEntry(
                model_id, model, variables,
                image_size=(image_size if image_size is not None
                            else (primary.image_size if primary
                                  else image_max_height)),
                img_num=(img_num if img_num is not None
                         else (primary.img_num if primary
                               else _default_img_num)),
                dtype=dtype, wire=self.wire,
                multi_frame=self._multi_frame_opt)
            self._models[model_id] = entry
        if entry.dtype != "f32":
            _logger.info("model %r quantized to %s: %s", model_id,
                         entry.dtype, quant_summary(entry.variables))
        self.metrics.ready = False         # one cold model => not ready

    def entry(self, model_id: Optional[str] = None) -> _ModelEntry:
        """The table entry for ``model_id`` (None = primary); unknown ids
        are a loud error, never a fallback to some other model."""
        if model_id is None:
            model_id = self.default_model_id
        try:
            return self._models[model_id]
        except KeyError:
            raise ValueError(
                f"unknown model {model_id!r}; this engine serves "
                f"{self.model_ids()}") from None

    def has_model(self, model_id: str) -> bool:
        return model_id in self._models

    def model_fingerprint(self, model_id: Optional[str] = None) -> str:
        """The checkpoint fingerprint of one model (None = primary): a
        stable hex digest of its host params tree + serving dtype.  This
        is the weight identity the verdict cache keys on and ``/readyz``
        publishes per model — a hot reload or quantized swap changes it
        atomically with the weights."""
        return self.entry(model_id).fingerprint

    def model_ids(self) -> Tuple[str, ...]:
        return tuple(self._models)

    # --- single-model back-compat surface (primary entry) -------------
    @property
    def model(self):
        return self.entry().model

    @property
    def image_size(self) -> int:
        return self.entry().image_size

    @property
    def img_num(self) -> int:
        return self.entry().img_num

    @property
    def multi_frame(self) -> bool:
        return self.entry().multi_frame

    @property
    def _variables(self):
        return self.entry().variables

    @property
    def _host_template(self):
        return self.entry().host_template

    @property
    def reload_count(self) -> int:
        return sum(e.reload_count for e in self._models.values())

    # ------------------------------------------------------------------
    # wire / program shapes
    # ------------------------------------------------------------------
    def _entry_wire_spec(self, entry: _ModelEntry) -> Tuple[int, Any]:
        """(channels, dtype) of one SINGLE-frame wire sample."""
        if self.wire == "uint8":
            return 3, np.uint8
        return 3 * entry.img_num, np.float32

    @property
    def _wire_spec(self) -> Tuple[int, Any]:
        return self._entry_wire_spec(self.entry())

    def _entry_chans(self, entry: _ModelEntry) -> Tuple[int, ...]:
        """Channel widths this entry compiles (one program per width per
        bucket): the single-frame wire width plus, on a multi-frame uint8
        wire, the channel-concatenated clip width."""
        base, _ = self._entry_wire_spec(entry)
        if entry.multi_frame:
            return (base, 3 * entry.img_num)
        return (base,)

    def allowed_chans(self, model_id: Optional[str] = None
                      ) -> Tuple[int, ...]:
        """Channel counts a request array may carry on this wire."""
        return self._entry_chans(self.entry(model_id))

    def _make_program(self, entry: _ModelEntry, chans: int):
        """The traced score function for one (model, channel-width): the
        uint8 wire fuses normalize (+ replicate) with the model, and
        quantized params dequantize in-trace (realize_tree — a no-op at
        f32, preserving the CLI bit-parity contract)."""
        model, n_rep = entry.model, entry.img_num
        if self.wire == "uint8":
            replicate = (chans == 3 and n_rep > 1)

            def _score(variables, x_u8, mean, std):
                x = (x_u8.astype(jnp.float32) - mean) / std
                if replicate:
                    x = jnp.tile(x, (1, 1, 1, n_rep))
                logits = model.apply(realize_tree(variables), x,
                                     training=False)
                return jax.nn.softmax(logits, axis=-1)
        else:
            def _score(variables, x):
                logits = model.apply(realize_tree(variables), x,
                                     training=False)
                return jax.nn.softmax(logits, axis=-1)
        return _score

    def _run(self, entry: _ModelEntry, bucket: int, chans: int,
             variables, x):
        ex = entry.compiled[(bucket, chans)]
        if self.wire == "uint8":
            if chans == 3:
                return ex(variables, x, entry.mean, entry.std)
            return ex(variables, x, entry.mean_multi, entry.std_multi)
        return ex(variables, x)

    # ------------------------------------------------------------------
    # compile cache
    # ------------------------------------------------------------------
    @property
    def compile_count(self) -> int:
        return self.metrics.compiles_total.value

    @property
    def ready(self) -> bool:
        return self.metrics.ready

    def readiness_detail(self) -> Dict[str, Any]:
        """The ``/readyz`` JSON body: per-model readiness + the health
        signals a fleet router scrapes.  A 503 with this body means
        "process up, serving set not ready" (cold model warming,
        watchdog re-warm, reload canary) — distinguishable from "engine
        down" (no response at all) without parsing metrics text."""
        return {
            "ready": bool(self.metrics.ready),
            # degraded = ready on a SUBSET of buckets while the rest warm
            # in background (staged warmup); the router's scraper routes
            # any 200, so degraded capacity is routable by construction
            "phase": self._phase,
            # snapshot: a live add_model grows the table from another
            # thread (the PR 14 warmup/_rewarm discipline)
            "models": {
                mid: {"warmed": e.warmed,
                      "image_size": e.image_size,
                      "img_num": e.img_num,
                      "dtype": e.dtype,
                      "fingerprint": e.fingerprint,
                      "reloads": e.reload_count,
                      "warm_buckets": sorted(
                          {b for (b, _c) in list(e.compiled)})}
                for mid, e in list(self._models.items())},
            "breaker": self.breaker.state,
            # the device the executables run on, as jax reports it — a
            # chip deployment that came up on the CPU is visible here
            "device": {"platform": jax.devices()[0].platform,
                       "kind": jax.devices()[0].device_kind},
            "queue_depth": int(self.metrics.queue_depth),
            "inflight": int(self.metrics.inflight),
        }

    def _warm_order(self) -> Tuple[int, ...]:
        """Bucket warm order: the configured priority first, remaining
        buckets smallest-first (small buckets compile fastest and already
        serve single requests — the best capacity-per-second spent)."""
        rest = [b for b in self.buckets if b not in self._warm_priority]
        return self._warm_priority + tuple(rest)

    def warmup(self, staged: bool = False) -> None:
        """Obtain every (model, bucket, chans) executable — from the
        warm-start store when attached, else a fresh AOT compile — and
        execute each once (primes any first-run allocation paths), then
        flip ready.  Idempotent per entry: adding a model to a warmed
        engine only builds the new entry's programs.

        ``staged=True`` warms only the FIRST priority bucket before
        declaring readiness (phase ``degraded``: /readyz goes 200, the
        dispatch path pads into the already-warm buckets only) and warms
        the remaining buckets on a background thread, flipping the phase
        to ``ready`` when the full set is live.  A recovery firing
        mid-stage aborts the background warm — the recovery generation
        owns readiness and the warmed subset keeps serving."""
        gen = self._gen
        t0 = time.monotonic()
        compile0 = self.metrics.warmup_seconds["compile"]
        order = self._warm_order()
        first, rest = order[:1], order[1:]
        # snapshot: a concurrent add_model may grow the table mid-loop
        for entry in list(self._models.values()):
            self._warm_entry(entry, buckets=(first if staged and rest
                                             else order))
        # the live add_model path runs this on the caller's thread while
        # the watchdog (or a reload canary) may be mid-recovery: only the
        # generation that was current for the WHOLE warmup may declare
        # readiness — a recovery in between owns the flag (its own
        # re-warm proves the device before it restores ready)
        with self._recover_lock:
            if gen == self._gen:
                self._phase = "degraded" if staged and rest else "ready"
                self.metrics.ready = True
        self.last_warmup_wall = time.monotonic() - t0
        # warm = everything warmup did beyond obtaining executables
        # (execute-once priming, canaries, store serialization)
        self.metrics.warmup_seconds["warm"] += max(
            0.0, self.last_warmup_wall
            - (self.metrics.warmup_seconds["compile"] - compile0))
        if staged and rest:
            t = threading.Thread(target=self._warm_rest,
                                 args=(gen, rest), daemon=True,
                                 name="serving-warm-bg")
            self._warm_thread = t
            t.start()

    def _warm_rest(self, gen: int, buckets: Tuple[int, ...]) -> None:
        """Background half of a staged warmup: one bucket at a time, so
        dispatch sees capacity grow between buckets, not after all."""
        try:
            for b in buckets:
                if gen != self._gen or self._stop.is_set():
                    return             # a recovery owns readiness now
                for entry in list(self._models.values()):
                    self._warm_entry(entry, buckets=(b,))
            with self._recover_lock:
                if gen == self._gen:
                    self._phase = "ready"
        except Exception:                              # noqa: BLE001
            _logger.exception("staged warmup: background bucket warm "
                              "failed; engine stays degraded on the "
                              "already-warm buckets")

    # -- warm-start store plumbing -------------------------------------
    def _store_fields(self, entry: _ModelEntry, bucket: int,
                      chans: int) -> Dict[str, Any]:
        """The complete warmstart key fields of one executable (see
        serving/warmkey.py).  The program hash digests the model config
        (flax dataclass repr), the *signature* of the quantized params
        tree (paths/shapes/dtypes — weights are call arguments, so
        checkpoints of one architecture share executables) and the
        normalization constants; quant/wire/geometry ride as their own
        loud fields."""
        from . import warmkey
        h = hashlib.sha256()
        h.update(repr(entry.model).encode())
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                entry.variables)[0]:
            h.update(jax.tree_util.keystr(path).encode())
            h.update(str(jnp.shape(leaf)).encode())
            h.update(str(jnp.result_type(leaf)).encode())
        for a in (entry.mean, entry.std, entry.mean_multi,
                  entry.std_multi):
            h.update(np.asarray(a).tobytes())
        dev = jax.devices()[0]
        return warmkey.key_fields(
            backend=jax.default_backend(),
            device_kind=dev.device_kind,
            program=h.hexdigest(),
            geometry={"image_size": entry.image_size,
                      "img_num": entry.img_num,
                      "multi_frame": entry.multi_frame,
                      "model_class": type(entry.model).__name__},
            bucket=bucket, chans=chans, wire=self.wire,
            quant=entry.dtype, sharding="")

    def _warm_golden_input(self, entry: _ModelEntry, bucket: int,
                           chans: int) -> np.ndarray:
        """Deterministic canary input for one (bucket, chans): identical
        across processes (fixed seed), so manifest golden scores from the
        serializing process can demand bit-exactness in the loading one."""
        s = entry.image_size
        _, dtype = self._entry_wire_spec(entry)
        rng = np.random.default_rng(0xCA9A87)
        if np.dtype(dtype) == np.uint8:
            return rng.integers(0, 256, (bucket, s, s, chans),
                                dtype=np.uint8)
        return rng.random((bucket, s, s, chans), dtype=np.float32)

    def _store_load(self, entry: _ModelEntry, bucket: int, chans: int):
        """Try the store for one executable.  Returns ``(compiled,
        (fields, manifest))`` or None (counted miss/fallback)."""
        if self.warmstart is None:
            return None
        from .warmstart import WarmstartMiss
        fields = self._store_fields(entry, bucket, chans)
        try:
            compiled, manifest = self.warmstart.load(
                fields, execution_devices=jax.devices()[:1])
        except WarmstartMiss as e:
            if e.reason == "absent":
                self.metrics.warmstart_misses_total.inc()
            else:
                # present but unusable — corrupt blob, foreign manifest,
                # version skew baked into the key fields: fall back to a
                # fresh compile, loudly, and re-serialize over it
                self.metrics.warmstart_fallbacks_total.inc()
                _logger.warning(
                    "warmstart: %s bucket %d (%dch): %s — compiling "
                    "fresh", entry.model_id, bucket, chans, e)
            return None
        self.metrics.warmstart_hits_total.inc()
        return compiled, (fields, manifest)

    def _warm_canary(self, entry: _ModelEntry, bucket: int, chans: int,
                     fields: Dict[str, Any],
                     manifest: Dict[str, Any]) -> bool:
        """Golden-batch gate for ONE deserialized executable: scores must
        be finite and shape-correct, and — when the manifest was written
        under the currently-served checkpoint (fingerprint match, the
        scale-up common path) — bit-exact against the recorded scores.
        A fingerprint-skewed entry that passes gets its manifest
        re-stamped so the next same-checkpoint spawn regains the
        bit-exact gate."""
        from . import warmkey
        gx = self._warm_golden_input(entry, bucket, chans)
        why = ""
        scores: Optional[np.ndarray] = None
        try:
            scores = np.asarray(self._run(entry, bucket, chans,
                                          entry.variables, gx))
        except Exception as e:                         # noqa: BLE001
            why = f"execution failed: {e}"
        if why == "" and (scores.ndim != 2 or scores.shape[0] != bucket):
            why = f"scores shape {scores.shape} for bucket {bucket}"
        if why == "" and not np.isfinite(scores).all():
            why = "non-finite scores"
        same_ckpt = (manifest.get("params_fingerprint")
                     == entry.fingerprint)
        if why == "" and same_ckpt:
            try:
                ref = warmkey.decode_array(manifest["golden_scores"])
            except Exception as e:                     # noqa: BLE001
                why = f"manifest golden scores unreadable: {e}"
            else:
                if ref.shape != scores.shape or \
                        not np.array_equal(ref, scores):
                    why = ("scores not bit-identical to the manifest's "
                           "(same checkpoint fingerprint)")
        if why:
            self.metrics.warmstart_canary_rejects_total.inc()
            _logger.error("warmstart: canary REJECTED deserialized "
                          "executable %s bucket %d (%dch): %s — "
                          "recompiling fresh", entry.model_id, bucket,
                          chans, why)
            return False
        if not same_ckpt and self.warmstart is not None:
            self.warmstart.refresh_manifest(
                fields, golden_scores=scores,
                params_fingerprint=entry.fingerprint)
        return True

    def _store_save(self, entry: _ModelEntry, bucket: int,
                    chans: int) -> None:
        if self.warmstart is None:
            return
        fields = self._store_fields(entry, bucket, chans)
        gx = self._warm_golden_input(entry, bucket, chans)
        scores = np.asarray(self._run(entry, bucket, chans,
                                      entry.variables, gx))
        if self.warmstart.save(fields, entry.compiled[(bucket, chans)],
                               golden_scores=scores,
                               params_fingerprint=entry.fingerprint,
                               execution_devices=jax.devices()[:1]):
            self.metrics.warmstart_serialized_total.inc()

    def _compile_units(self, entry: _ModelEntry,
                       units: List[Tuple[int, int]]) -> None:
        """Fresh-compile the given (bucket, chans) units, dispatching
        independent compiles concurrently: ``lower()`` traces under the
        GIL but ``compile()`` releases it inside XLA, so a thread pool
        overlaps the bucket compiles (the wall win materializes with
        spare cores; the per-unit walls in ``warm_compile_walls`` always
        prove the overlap).  Metrics/store writes stay on the caller's
        thread."""
        if not units:
            return
        s = entry.image_size
        _, dtype = self._entry_wire_spec(entry)

        def _build(unit: Tuple[int, int]):
            b, chans = unit
            t0 = time.monotonic()
            x_spec = jax.ShapeDtypeStruct((b, s, s, chans),
                                          jnp.dtype(dtype))
            fn = self._make_program(entry, chans)
            # per-bucket AOT lowering is the POINT of this loop: one
            # deliberate compile per declared (model, bucket, chans)
            # at warmup, counted in compiles_total, zero recompiles
            # after ready
            if self.wire == "uint8":
                mean, std = (entry.mean, entry.std) if chans == 3 \
                    else (entry.mean_multi, entry.std_multi)
                lowered = jax.jit(fn).lower(entry.variables, x_spec,
                                            mean, std)
            else:
                lowered = jax.jit(fn).lower(entry.variables, x_spec)
            return unit, lowered.compile(), time.monotonic() - t0

        workers = self._warm_parallel if self._warm_parallel > 0 \
            else min(4, len(units))
        if workers <= 1 or len(units) == 1:
            results = [_build(u) for u in units]
        else:
            with ThreadPoolExecutor(
                    max_workers=min(workers, len(units)),
                    thread_name_prefix="serving-warm-compile") as pool:
                results = list(pool.map(_build, units))
        for unit, compiled, wall in results:
            entry.compiled[unit] = compiled
            self.warm_compile_walls[unit] = wall
            self.metrics.compiles_total.inc()
            _logger.info("model %r bucket %d (%dch) compiled in %.1fs",
                         entry.model_id, unit[0], unit[1], wall)

    def _warm_entry(self, entry: _ModelEntry,
                    buckets: Optional[Sequence[int]] = None) -> None:
        """Bring one entry's executables live for ``buckets`` (None =
        the full warm order): store-deserialize what the warm-start tier
        has (canary-gated), fresh-compile the rest (concurrently), warm-
        execute every new unit once, then (re)serialize fresh compiles."""
        warm_buckets = tuple(buckets) if buckets is not None \
            else self._warm_order()
        s = entry.image_size
        _, dtype = self._entry_wire_spec(entry)
        units = [(b, chans) for chans in self._entry_chans(entry)
                 for b in warm_buckets if (b, chans) not in entry.compiled]
        t_compile0 = time.monotonic()
        loaded: Dict[Tuple[int, int], Tuple[Dict, Dict]] = {}
        misses: List[Tuple[int, int]] = []
        for unit in units:
            got = self._store_load(entry, *unit)
            if got is not None:
                entry.compiled[unit] = got[0]
                self.warm_compile_walls[unit] = 0.0
                loaded[unit] = got[1]
            else:
                misses.append(unit)
        self._compile_units(entry, misses)
        self.metrics.warmup_seconds["compile"] += \
            time.monotonic() - t_compile0
        # canary-gate every deserialized executable BEFORE it can serve;
        # a reject is evicted, recompiled fresh and re-serialized over
        for unit, (fields, manifest) in loaded.items():
            if not self._warm_canary(entry, unit[0], unit[1], fields,
                                     manifest):
                entry.compiled.pop(unit, None)
                self._compile_units(entry, [unit])
                misses.append(unit)
        # one warm execution per new unit primes first-run allocations
        # (host zeros + device_put: a jnp.zeros fill would compile a tiny
        # broadcast program and break the warm path's zero-compile bar)
        for b, chans in units:
            jax.block_until_ready(self._run(
                entry, b, chans, entry.variables,
                jax.device_put(np.zeros((b, s, s, chans), dtype))))
        for unit in misses:
            self._store_save(entry, *unit)
        # golden canary batch: a fixed seeded input whose scores under the
        # CURRENT weights baseline both the reload canary and (optionally)
        # its drift tolerance — tied to the canonical smallest bucket, so
        # a staged/priority warm that hasn't built it yet defers to the
        # _warm_entry call that does
        base_chans, dtype = self._entry_wire_spec(entry)
        if (self.buckets[0], base_chans) in entry.compiled:
            if entry.golden is None:
                entry.golden = self._warm_golden_input(
                    entry, self.buckets[0], base_chans)
            entry.golden_ref = np.asarray(
                self._run(entry, self.buckets[0], base_chans,
                          entry.variables, entry.golden))
        entry.warmed = True

    def _rewarm(self) -> None:
        """Execute every AOT (model, bucket, chans) executable once
        against the serving weights (the recovery path's proof that the
        device answers again).  Runs the EXISTING compiled executables —
        a recovery never recompiles, which is what lets chaos_serve
        assert zero post-recovery backend compiles.  Snapshot the table:
        a timed-out recovery releases _recover_lock while this probe is
        still running, so a live add_model may grow the dict mid-loop
        (the new entry's own warmup proves it; this probe owes it
        nothing)."""
        for entry in list(self._models.values()):
            if not entry.warmed:
                continue       # cold add_model entry: no executables yet
            s = entry.image_size
            _, dtype = self._entry_wire_spec(entry)
            # the executables that exist, not the full bucket grid: a
            # staged warmup may still be building the tail buckets
            for b, chans in sorted(list(entry.compiled)):
                jax.block_until_ready(self._run(
                    entry, b, chans, entry.variables,
                    jax.device_put(np.zeros((b, s, s, chans), dtype))))
        self.metrics.rewarms_total.inc()

    # ------------------------------------------------------------------
    # scoring
    # ------------------------------------------------------------------
    def _chans_of(self, entry: _ModelEntry, array) -> int:
        """Wire channel count of one request array, validated against the
        entry's compiled programs (unknown widths must fail loudly here,
        never reach an uncompiled shape)."""
        chans = int(np.shape(array)[-1]) if np.ndim(array) else 0
        if chans not in self._entry_chans(entry):
            raise ValueError(
                f"request carries {chans} channels; model "
                f"{entry.model_id!r} accepts {self._entry_chans(entry)} "
                f"(wire={self.wire}, img_num={entry.img_num}, "
                f"multi_frame={entry.multi_frame})")
        return chans

    def _warm_buckets(self, entry: _ModelEntry,
                      chans: int) -> Tuple[int, ...]:
        """Buckets with a LIVE executable for this channel width — the
        only shapes dispatch may pad into.  During a staged warmup this
        is a growing prefix of the bucket grid; fully warmed it equals
        ``self.buckets``.  ``list()`` snapshots against the background
        warm thread growing the dict mid-iteration."""
        avail = sorted(b for (b, c) in list(entry.compiled) if c == chans)
        return tuple(avail) if avail else self.buckets

    def _pad_batch(self, entry: _ModelEntry, arrays: List[np.ndarray],
                   chans: int) -> Tuple[np.ndarray, int]:
        n = len(arrays)
        bucket = pick_bucket(n, self._warm_buckets(entry, chans))
        s = entry.image_size
        _, dtype = self._entry_wire_spec(entry)
        # fresh buffer every batch: jax CPU device_put zero-copies aligned
        # host memory, so reusing one buffer would race the still-executing
        # previous batch (same hazard data/loader.py guards with
        # block_until_ready)
        buf = np.zeros((bucket, s, s, chans), dtype)
        for i, a in enumerate(arrays):
            write_into = getattr(a, "write_into", None)
            if write_into is not None:
                # streaming FrameStack payload (streaming/ring.py): the
                # window's frames gather straight from the crop ring into
                # this slab row — the ONE copy of the window's life —
                # and the ring pins release
                write_into(buf[i])
            else:
                buf[i] = a
        return buf, bucket

    def score_batch(self, arrays: List[np.ndarray],
                    model_id: Optional[str] = None) -> np.ndarray:
        """Synchronous scoring of up to max-bucket wire-format samples
        (tests, warm checks) against one model; one uniform channel width
        per call — the serving path goes through stage/complete instead
        and may mix widths and models."""
        entry = self.entry(model_id)
        chans = self._chans_of(entry, arrays[0])
        for a in arrays[1:]:
            if self._chans_of(entry, a) != chans:
                raise ValueError("score_batch arrays must share one "
                                 "channel width; the async path handles "
                                 "mixed single/multi-frame traffic")
        buf, bucket = self._pad_batch(entry, arrays, chans)
        out = self._run(entry, bucket, chans, entry.variables,
                        jax.device_put(buf))
        return np.asarray(out)[:len(arrays)]

    def _stage(self, requests: List[Request]) -> List[_Staged]:
        """Dispatch requests as one device batch per (model, channel
        width).

        Requests for different models (or different frame layouts) ride
        different compiled programs, so a coalesced batch that mixes them
        splits into staged sub-batches — each still a pre-compiled
        bucket, dispatched back-to-back so all overlap the previous
        batch's completion.  Every sub-batch enters the ``_pending``
        ledger at dispatch so the watchdog sees its age."""
        groups: Dict[Tuple[str, int], List[Request]] = {}
        for r in requests:
            # per-request validation: an unknown model id or channel
            # width (possible on direct library submits — the HTTP edge
            # pre-validates) must fail THAT request, never the whole
            # coalesced batch (which would 500 innocent riders and feed
            # the circuit breaker a non-device failure)
            try:
                entry = self.entry(r.model_id)
                key = (entry.model_id, self._chans_of(entry, r.array))
            except ValueError as e:
                if r.claim():
                    self.metrics.failed_total.inc()
                    self.metrics.count_model("failed", r.model_id)
                    r.set_exception(e)
                continue
            groups.setdefault(key, []).append(r)
        staged: List[_Staged] = []
        try:
            for (model_id, chans), grp in groups.items():
                entry = self._models[model_id]
                # during a staged warmup the coalesced group may exceed
                # the largest LIVE bucket: split it — each chunk is still
                # a pre-compiled bucket, dispatched back-to-back (fully
                # warmed, cap == max_batch and this is one chunk)
                warm = self._warm_buckets(entry, chans)
                cap = warm[-1]
                for i0 in range(0, len(grp), cap):
                    sub = grp[i0:i0 + cap]
                    seq = self._batch_seq
                    self._batch_seq += 1
                    if self.chaos.active and \
                            self.chaos.fires("serve_exc", seq):
                        self.metrics.count_chaos("serve_exc")
                        raise RuntimeError(
                            f"chaos: injected score-fn exception "
                            f"(batch {seq})")
                    with TraceAnnotation(
                            "dfd.serve.stage", batch=seq, rows=len(sub),
                            bucket=pick_bucket(len(sub), warm)):
                        buf, bucket = self._pad_batch(
                            entry, [r.array for r in sub], chans)
                        out = self._run(entry, bucket, chans,
                                        entry.variables, jax.device_put(buf))
                    now = time.monotonic()
                    for r in sub:
                        r.timings["queue"] = now - r.enqueue_t
                    st = _Staged(sub, out, bucket, now, seq, model_id)
                    # gauge bump + ledger entry are ONE atom vs the
                    # recovery path (which zeroes the gauge and clears
                    # the ledger under the same lock) — split, a recovery
                    # landing between them would leave the inflight gauge
                    # permanently negative
                    with self._pending_lock:
                        self.metrics.inflight += len(sub)
                        self._pending.append(st)
                    staged.append(st)
        except Exception:
            # a later group poisoned the stage: the caller fails EVERY
            # request of the coalesced batch, so unwind the sub-batches
            # already dispatched (their device work is wasted, not leaked)
            for st in staged:
                self._unpend(st)
            raise
        return staged

    def _unpend(self, staged: _Staged) -> bool:
        """Claim a staged batch out of the in-flight ledger; the claim
        carries its inflight-gauge decrement (one atom, same lock as the
        recovery path's clear-and-zero).  False = a recovery already
        claimed it — the caller owns neither the gauge nor the
        requests."""
        with self._pending_lock:
            try:
                self._pending.remove(staged)
            except ValueError:
                return False
            self.metrics.inflight -= len(staged.requests)
            return True

    def _complete(self, staged: _Staged, gen: int) -> None:
        if gen != self._gen:
            return                 # recovery owns these requests now
        if self.chaos.active and self.chaos.fires("serve_hang", staged.seq):
            hang_s = self.chaos.arg("serve_hang", 30.0)
            self.metrics.count_chaos("serve_hang")
            _logger.error("chaos: hanging completion of batch %d for "
                          "%.1fs", staged.seq, hang_s)
            time.sleep(hang_s)
        scores = np.asarray(staged.out)          # blocks on the device
        now = time.monotonic()
        if gen != self._gen or not self._unpend(staged):
            # the watchdog recovered while we were blocked: it already
            # failed these requests and zeroed the gauges — touch nothing
            # (the ledger claim is the tiebreaker for the last-instant
            # race between the gen check and the recovery's clear)
            return
        if self.chaos.active and self.chaos.fires("serve_nan", staged.seq):
            self.metrics.count_chaos("serve_nan")
            scores = np.full_like(scores, np.nan)
        device_dt = now - staged.dispatch_t
        n = len(staged.requests)
        m = self.metrics
        if not np.isfinite(scores[:n]).all():
            # a non-finite score is NEVER served: fail every rider with a
            # 503-mapped error and let the breaker see the batch failure
            m.nonfinite_batches_total.inc()
            self.breaker.record_failure()
            _logger.error("device batch %d produced non-finite scores; "
                          "failing %d request(s)", staged.seq, n)
            self._fail(staged.requests, NonFiniteScores(
                f"device batch {staged.seq} produced non-finite scores "
                f"(bucket {staged.bucket}); retry against healthy weights"))
            return
        m.batches_total.inc()
        m.batch_rows_total.inc(n)
        m.padded_rows_total.inc(staged.bucket - n)
        m.count_bucket_rows(staged.model_id, staged.bucket, n,
                            staged.bucket - n)
        m.latency["device"].observe(device_dt)
        m.count_completion(n, now)
        for i, r in enumerate(staged.requests):
            r.timings["device"] = device_dt
            m.latency["queue"].observe(r.timings.get("queue", 0.0))
            if r.claim():
                m.scored_total.inc()
                m.count_model("scored", r.model_id)
                r.set_result(scores[i])
        self.breaker.record_success()

    def _fail(self, requests: List[Request], err: BaseException) -> None:
        for r in requests:
            if r.claim():
                self.metrics.failed_total.inc()
                self.metrics.count_model("failed", r.model_id)
                r.set_exception(err)

    # ------------------------------------------------------------------
    # serve loop
    # ------------------------------------------------------------------
    @staticmethod
    def _out_ready(out) -> bool:
        try:
            return bool(out.is_ready())
        except AttributeError:        # pragma: no cover — very old jax
            return True

    def _loop_once(self, batcher: MicroBatcher, gen: int) -> None:
        if self.chaos.active and \
                self.chaos.fires("serve_kill", self._batch_seq):
            self.metrics.count_chaos("serve_kill")
            _logger.error("chaos: killing engine worker (gen %d)", gen)
            # SystemExit ends the worker thread outright (serve_loop's
            # crash recovery deliberately does not absorb it) — the
            # watchdog's worker-liveness probe is what must bring
            # serving back
            raise SystemExit("chaos: serve_kill")
        self._maybe_apply_reload()
        profiler = self.profiler
        if profiler is not None:
            # cheap flag check when idle; opens and closes a trace window
            # counted in device batches
            profiler.on_step(self._batch_seq)
        with self._pending_lock:
            pending = list(self._pending)
        if not pending:
            # device idle: block for the first request, then coalesce
            # within the deadline window
            if profiler is not None:
                profiler.poll()         # PROFILE trigger file: 1 stat
            with TraceAnnotation("dfd.serve.wait"):
                requests = batcher.next_batch(timeout=0.05)
            if requests:
                try:
                    self._stage(requests)
                except Exception as e:             # noqa: BLE001
                    self._fail(requests, e)        # poisoned batch: 500s
                    self.breaker.record_failure()
                    raise                          # now, not at timeout
            return
        # Device busy on batch k: its execution time is FREE coalescing
        # time — gather batch k+1 until k's result lands AND the deadline
        # window has run, or the bucket fills (short-poll takes so
        # is_ready is re-checked ~1ms), then a last non-blocking drain for
        # stragglers already queued.  Honoring the deadline window here
        # too matters under closed-loop load: responses fan out staggered,
        # so the resends of batch k's clients arrive over several ms — a
        # gather that stops the instant the device idles locks into a
        # small-batch equilibrium (tiny batch → short exec → short gather
        # → tiny batch again).
        requests: List[Request] = []
        out = pending[-1].out              # last sub-batch lands last
        flush_at = time.monotonic() + batcher.deadline_s
        with TraceAnnotation("dfd.serve.gather"):
            while len(requests) < batcher.max_batch and gen == self._gen:
                if self._out_ready(out) and time.monotonic() >= flush_at:
                    break
                r = batcher.take(timeout=0.001)
                if r is not None:
                    requests.append(r)
            while len(requests) < batcher.max_batch and gen == self._gen:
                r = batcher.take(timeout=0.0)
                if r is None:
                    break
                requests.append(r)
        if gen != self._gen:
            # a recovery fired while we gathered (a REAL device hang parks
            # the worker right here, endlessly re-polling is_ready): the
            # dequeued requests would otherwise be stranded — fail them
            self._fail(requests, EngineStalled(
                "engine restarted while this request was being batched"))
            return
        # dispatch k+1 (async) BEFORE blocking on k: transfer + compute of
        # k+1 overlap k's completion — the DeviceLoader double buffer
        if requests:
            try:
                self._stage(requests)
            except Exception as e:                 # noqa: BLE001
                self._fail(requests, e)
                self.breaker.record_failure()
                raise
        err: Optional[Exception] = None
        for st in pending:
            try:
                with TraceAnnotation("dfd.serve.complete", batch=st.seq):
                    self._complete(st, gen)
            except Exception as e:                 # noqa: BLE001
                if gen != self._gen:
                    return             # recovery already owns the ledger
                self._unpend(st)       # claim carries the gauge decrement
                self._fail(st.requests, e)
                self.breaker.record_failure()
                err = e
        if err is not None:
            raise err

    def serve_loop(self, batcher: MicroBatcher, gen: int = 0) -> None:
        """Run until stop() or a newer worker generation supersedes this
        one; never lets an exception strand requests or kill the worker
        (an injected SystemExit — the worker-kill chaos — does end the
        thread, and the watchdog's liveness probe recovers from it)."""
        while not self._stop.is_set() and gen == self._gen:
            try:
                self._loop_once(batcher, gen)
            except SystemExit:
                # the worker-kill chaos: die like a crashed thread (the
                # watchdog must notice and respawn) but without tripping
                # pytest's thread-exception hook — matching Python's own
                # silent-SystemExit thread semantics
                return
            except Exception:                      # noqa: BLE001
                # _loop_once already failed the requests of whichever batch
                # crashed; self._pending (if any) is a healthy dispatched
                # batch the next iteration will complete — don't touch it
                if gen != self._gen:
                    return
                _logger.exception("engine worker crashed; recovering")
                self.metrics.worker_restarts_total.inc()
                time.sleep(0.01)     # a persistent fault must not spin-log

    def _spawn_worker(self) -> None:
        gen = self._gen
        self._worker = threading.Thread(
            target=self.serve_loop, args=(self._batcher, gen),
            name=f"serving-engine-g{gen}", daemon=True)
        self._worker.start()

    def start(self, batcher: MicroBatcher) -> None:
        assert self._batcher is None, "engine already started"
        self._batcher = batcher
        # unrouted submits land on the primary model's books
        batcher.default_model_id = self.default_model_id
        # verdict cache: the batcher's probe keys on the engine's weight
        # identity — a submit races a reload only in the safe direction
        # (new scores stored under the orphaned old fingerprint, never
        # old scores under the new one)
        batcher.fingerprint_of = self.model_fingerprint
        if self.verdict_cache is not None and batcher.cache is None:
            batcher.cache = self.verdict_cache
        self._spawn_worker()
        self.watchdog.start()

    def stop(self) -> None:
        self._stop.set()
        self.watchdog.stop()       # before the join: a recovery must not
        if self._worker is not None:    # race the shutdown
            self._worker.join(timeout=5.0)
            self._worker = None
        with self._pending_lock:
            pending, self._pending = self._pending, []
        for st in pending:
            self._fail(st.requests, RuntimeError("server shutting down"))
        if self.profiler is not None:
            self.profiler.close()      # ends an open capture, frees SIGUSR2

    # ------------------------------------------------------------------
    # watchdog recovery (serving/resilience.py runs the monitor thread)
    # ------------------------------------------------------------------
    def _oldest_dispatch(self) -> Optional[float]:
        with self._pending_lock:
            if not self._pending:
                return None
            return min(st.dispatch_t for st in self._pending)

    def _worker_alive(self) -> bool:
        return self._worker is None or self._worker.is_alive()

    def _recover(self, reason: str) -> None:
        """Watchdog-thread recovery: fail everything in flight, retire the
        current worker generation, prove the device answers by re-warming
        every AOT bucket of every model (readiness stays FALSE until it
        does), then start a fresh worker.  Zero recompiles by
        construction — the bucket executables survive the restart."""
        with self._recover_lock:
            if self._stop.is_set():
                return
            if self._rewarm_thread is not None and \
                    self._rewarm_thread.is_alive():
                # an earlier recovery's re-warm is still wedged on the
                # device: spawning another would just stack threads —
                # stay not-ready until the device answers or ops act
                return
            _logger.error("engine recovery (%s): failing in-flight "
                          "requests, restarting worker, re-warming %d "
                          "bucket(s) x %d model(s)", reason,
                          len(self.buckets), len(self._models))
            self.metrics.ready = False
            self.metrics.watchdog_recoveries_total.inc()
            self.breaker.record_failure()
            self._gen += 1         # neuters the old worker's late writes
            with self._pending_lock:
                # clear + zero under the ledger lock: pairs with _stage's
                # atomic {gauge bump, ledger append} and _unpend's atomic
                # {claim, gauge decrement}
                pending, self._pending = self._pending, []
                self.metrics.inflight = 0
            for st in pending:
                self._fail(st.requests, EngineStalled(
                    f"engine recovery ({reason}) abandoned this batch"))
            # bounded re-warm on a helper thread: against a genuinely
            # hung device, block_until_ready never returns — the watchdog
            # thread must stay free to keep polling (and to let stop()
            # shut down), so a re-warm that overruns its budget leaves
            # the engine not-ready and the next watchdog tick re-enters
            # here (the still-alive guard above keeps it single-flight)
            done = threading.Event()

            def _rewarm_probe():
                try:
                    self._rewarm()
                    done.set()
                except Exception:                  # noqa: BLE001
                    _logger.exception("post-recovery re-warm failed; "
                                      "engine stays not-ready")

            t = threading.Thread(target=_rewarm_probe, daemon=True,
                                 name="serving-rewarm")
            self._rewarm_thread = t
            t.start()
            deadline = time.monotonic() + self._rewarm_timeout_s
            while not done.wait(0.2):
                if self._stop.is_set():
                    return
                if time.monotonic() > deadline:
                    _logger.error(
                        "post-recovery re-warm still blocked after %.0fs "
                        "(device wedged?); engine stays not-ready",
                        self._rewarm_timeout_s)
                    return
                if not t.is_alive() and not done.is_set():
                    return             # probe raised; already logged
            self._rewarm_thread = None
            if self._batcher is not None:
                self._spawn_worker()
            self.metrics.ready = True
            _logger.info("engine recovered (%s): worker gen %d serving, "
                         "buckets re-warmed", reason, self._gen)

    # ------------------------------------------------------------------
    # hot weight reload
    # ------------------------------------------------------------------
    def submit_reload(self, host_tree: Any, source: str = "<api>",
                      model_id: Optional[str] = None) -> None:
        """Queue a host-side f32 variable tree for an atomic between-batch
        swap of one model's weights (called by the watcher threads, or
        directly in tests)."""
        if model_id is None:
            model_id = self.default_model_id
        with self._reload_lock:
            self._reload_box[model_id] = (host_tree, source)

    def _maybe_apply_reload(self) -> None:
        with self._reload_lock:
            if not self._reload_box:
                return
            model_id, (host_tree, source) = self._reload_box.popitem()
        try:
            entry = self.entry(model_id)
        except ValueError:
            _logger.error("reload for unknown model %r dropped", model_id)
            self.metrics.reload_errors_total.inc()
            return
        # Readiness must not lie while the canary runs: the worker thread
        # is busy proving the candidate weights, not dispatching batches,
        # so /readyz drops for the canary window (/healthz stays up) and
        # load balancers can route around the pause.  `gen` is captured
        # so a watchdog recovery firing mid-canary wins every race: the
        # stale worker neither commits the swap nor touches the ready
        # flag the recovery now owns — the reload attempt is requeued
        # for the fresh worker instead.
        gen = self._gen
        was_ready = self.metrics.ready
        self.metrics.ready = False
        try:
            if self._canary_hook is not None:      # test seam
                self._canary_hook()
            try:
                shapes = jax.tree.map(
                    lambda a: (tuple(np.shape(a)), np.asarray(a).dtype),
                    host_tree)
                if shapes != entry.var_shapes:
                    # a checkpoint of some OTHER model's tree lands here
                    # too: cross-model swaps are rejected loudly, never
                    # silently served
                    raise ValueError(
                        f"checkpoint tree/shape mismatch vs serving "
                        f"model {entry.model_id!r}")
                # the serving copy is quantized; the canary then gates
                # the QUANTIZED candidate — a quantization-broken swap
                # (NaN after dequant, drifted scores) rolls back here
                new_vars = jax.device_put(
                    quantize_tree(host_tree, entry.dtype))
                canary = self._canary_scores(entry, new_vars)
                # weight identity of the candidate, hashed OUTSIDE the
                # commit lock (bytes-proportional work) and assigned
                # inside it — one atom with the variables swap
                new_fp = _params_fingerprint(host_tree, entry.dtype)
            except Exception:                      # noqa: BLE001
                _logger.exception("hot reload of model %r from %s "
                                  "rejected; previous weights keep "
                                  "serving", entry.model_id, source)
                self.metrics.reload_errors_total.inc()
                return
            with self._recover_lock:   # serialize the commit vs recovery
                if gen != self._gen:
                    self.submit_reload(host_tree, source,
                                       model_id=model_id)   # retry fresh
                    return
                entry.variables = new_vars
                if canary is not None:
                    entry.golden_ref = canary      # new drift baseline
                # the fingerprint bump orphans every cached verdict of
                # the old weights: a stale hit is impossible from this
                # point on, no sweep required
                entry.fingerprint = new_fp
                entry.reload_count += 1
            if self.verdict_cache is not None:
                purged = self.verdict_cache.purge_model(
                    entry.model_id, keep_fingerprint=new_fp)
                if purged:
                    self.metrics.cache_invalidated_total.inc(purged)
                    self.metrics.cache_entries = self.verdict_cache.size()
            self.metrics.reloads_total.inc()
            self.metrics.count_model("reloads", entry.model_id)
            _logger.info("hot-reloaded model %r weights from %s "
                         "(reload #%d)", entry.model_id, source,
                         entry.reload_count)
        finally:
            with self._recover_lock:
                if gen == self._gen:
                    self.metrics.ready = was_ready

    def _canary_scores(self, entry: _ModelEntry,
                       new_vars) -> Optional[np.ndarray]:
        """Golden-batch canary: the candidate weights must produce finite,
        shape-correct scores — and, when ``reload_drift_tol`` >= 0, scores
        within that tolerance of the serving weights' on the SAME input —
        before they may serve.  Raises on any violation (the caller
        rejects and rolls back to the serving set).  Doubles as the aval-
        compatibility probe: it executes a compiled bucket with the new
        (quantized) params, so a dtype drift fails here, not on live
        traffic."""
        chans, dtype = self._entry_wire_spec(entry)
        if entry.golden is None:                   # warmup=False engines
            s = entry.image_size
            probe = self._run(
                entry, self.buckets[0], chans, new_vars,
                jax.device_put(
                    np.zeros((self.buckets[0], s, s, chans), dtype)))
            jax.block_until_ready(probe)
            return None
        canary = np.asarray(self._run(entry, self.buckets[0], chans,
                                      new_vars, entry.golden))
        if entry.golden_ref is not None and \
                canary.shape != entry.golden_ref.shape:
            self.metrics.reload_canary_failures_total.inc()
            raise ValueError(
                f"canary: golden-batch scores have shape {canary.shape}, "
                f"serving weights produce {entry.golden_ref.shape}")
        if not np.isfinite(canary).all():
            self.metrics.reload_canary_failures_total.inc()
            raise ValueError("canary: candidate weights produce "
                             "non-finite scores on the golden batch")
        if self.reload_drift_tol >= 0 and entry.golden_ref is not None:
            drift = float(np.max(np.abs(canary - entry.golden_ref)))
            if drift > self.reload_drift_tol:
                self.metrics.reload_canary_failures_total.inc()
                raise ValueError(
                    f"canary: golden-batch score drift {drift:.6g} "
                    f"exceeds --reload-drift-tol {self.reload_drift_tol}")
        return canary

    # ------------------------------------------------------------------
    def _newest_checkpoint(self, ckpt_dir: str
                           ) -> Optional[Tuple[str, float, int]]:
        try:
            names = os.listdir(ckpt_dir)
        except OSError:
            return None
        best = None
        for name in names:
            # dotfiles are never candidates (editor temps, the chaos
            # harness's torn copies)
            if name.startswith(".") or not name.endswith(_CKPT_SUFFIXES):
                continue
            path = os.path.join(ckpt_dir, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            key = (path, st.st_mtime, st.st_size)
            if best is None or key[1] > best[1]:
                best = key
        return best

    def _watch_loop(self, ckpt_dir: str, interval_s: float,
                    use_ema: bool, model_id: str) -> None:
        from ..models.helpers import load_checkpoint
        entry = self.entry(model_id)
        while not self._stop.wait(interval_s):
            newest = self._newest_checkpoint(ckpt_dir)
            if newest is None or newest == entry.last_reload_key:
                continue
            path = load_path = newest[0]
            seq = entry.reload_attempts
            entry.reload_attempts += 1
            if self.chaos.active and self.chaos.fires("torn_reload", seq):
                # route the load through a half-truncated copy so the
                # REAL torn-msgpack rejection (CheckpointCorrupt naming
                # the file) is what recovers, not a synthetic stand-in
                self.metrics.count_chaos("torn_reload")
                load_path = torn_copy(path, tempfile.gettempdir())
                _logger.error("chaos: reloading torn checkpoint copy %s",
                              load_path)
            try:
                loaded = load_checkpoint(entry.host_template, load_path,
                                         use_ema=use_ema, strict=False)
            except Exception:                      # noqa: BLE001
                _logger.exception("reload watcher (%s): cannot load %s; "
                                  "previous weights keep serving",
                                  entry.model_id, load_path)
                self.metrics.reload_errors_total.inc()
                if load_path == path:
                    # don't re-log a genuinely corrupt file every tick —
                    # but a chaos-torn COPY leaves the real file untried,
                    # so the next tick retries it clean (fire-once)
                    entry.last_reload_key = newest
                continue
            finally:
                if load_path != path:
                    try:
                        os.unlink(load_path)
                    except OSError:
                        pass
            entry.last_reload_key = newest
            self.submit_reload(loaded, source=path,
                               model_id=entry.model_id)

    def start_reload_watcher(self, ckpt_dir: str, interval_s: float = 5.0,
                             use_ema: bool = False,
                             model_id: Optional[str] = None) -> None:
        """Poll ``ckpt_dir`` for new ``models/helpers.py`` checkpoints and
        hot-swap them into ``model_id``'s slot (None = the primary
        model).  Writers must rename atomically into place (the repo's
        ``save_model_checkpoint`` does)."""
        entry = self.entry(model_id)
        assert entry.watcher is None, \
            f"watcher already started for model {entry.model_id!r}"
        # remember the current newest so only files appearing AFTER start
        # trigger a reload (the serving checkpoint itself usually lives in
        # the watched dir)
        entry.last_reload_key = self._newest_checkpoint(ckpt_dir)
        entry.watcher = threading.Thread(
            target=self._watch_loop,
            args=(ckpt_dir, interval_s, use_ema, entry.model_id),
            name=f"serving-reload-watcher-{entry.model_id}", daemon=True)
        if entry.model_id == self.default_model_id:
            self._watcher = entry.watcher      # single-model back-compat
        entry.watcher.start()
