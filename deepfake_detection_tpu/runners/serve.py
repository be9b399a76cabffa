"""Long-lived inference server entrypoint.

Where ``runners/test.py`` is the reference's one-shot CLI (pay interpreter
start + model build + XLA compile per invocation), this runner keeps one
process alive: params resident on device, every batch bucket AOT-compiled
before the first request, arrival-order traffic coalesced into those
buckets, overload shed with 429, and weights hot-swappable from a watched
checkpoint dir — the serving half of the ROADMAP's "heavy traffic" north
star, chip-independent (runs on CPU JAX identically).

Usage::

    python -m deepfake_detection_tpu.runners.serve \
        --model-path model.msgpack [--port 8377] [--buckets 1,4,16,64] \
        [--batch-deadline-ms 5] [--max-queue 128] [--reload-dir ckpts/]

    curl -s -X POST --data-binary @face.jpg -H 'Content-Type: image/jpeg' \
        http://127.0.0.1:8377/score

Scores are exactly ``runners/test.py``'s: same model build, same
checkpoint load paths, same preprocess split host/device
(tests/test_serving.py pins server == CLI bit-for-bit).
"""

from __future__ import annotations

import logging
import os
import signal
import sys
import threading
import time
from typing import Optional, Sequence

_logger = logging.getLogger(__name__)

__all__ = ["build_engine", "build_server", "main"]


def _skeleton_variables(model, image_size, in_chans):
    """Zero-compile variable skeleton: ``jax.eval_shape`` traces the
    init without building or running an executable, and host zeros fill
    the shapes.  ONLY valid under a strict (complete) checkpoint load,
    which overwrites every leaf — see ``_load_model_variables``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def _init(rng, dummy):
        return model.init({"params": rng, "dropout": rng}, dummy,
                          training=False)

    shapes = jax.eval_shape(
        _init, jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((1, image_size, image_size, in_chans),
                             jnp.float32))
    return jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes)


def _load_model_variables(model, model_path, *, image_size, in_chans,
                          use_ema, name):
    """Checkpoint load for one model-table entry, mirroring
    ``runners/test.py::test_img``."""
    import jax

    from ..models import init_model
    from ..models.helpers import load_checkpoint

    if model_path and os.path.isfile(model_path):
        # warm-start fast path (ISSUE 19): a checkpoint that strict-load
        # accepts overwrites EVERY leaf, so the init values are dead
        # weight — eval_shape skips the init jit (the bulk of the
        # params_load stage wall and its backend compile).  Any strict
        # failure (missing keys, shape drift) falls back to the real
        # init + lenient merge below, loudly.
        try:
            return load_checkpoint(
                _skeleton_variables(model, image_size, in_chans),
                model_path, use_ema=use_ema, strict=True)
        except Exception as e:                     # noqa: BLE001
            _logger.warning(
                "skeleton params load of %r failed (%s) — paying the "
                "full init for the lenient merge", name, e)
    variables = init_model(model, jax.random.PRNGKey(0),
                           (1, image_size, image_size, in_chans))
    if model_path and os.path.isdir(model_path):
        from ..train.checkpoint import load_sharded_for_eval
        variables = load_sharded_for_eval(model_path, variables)
    elif model_path:
        variables = load_checkpoint(variables, model_path,
                                    use_ema=use_ema, strict=False)
    else:
        _logger.warning("no checkpoint for model %r: serving a seed-0 "
                        "random init (bench/demo mode)", name)
    return variables


def build_engine(cfg):
    """Model table → warmed engine + micro-batcher + metrics — the device
    half every front end shares (``runners/serve.py``'s single-request
    HTTP server and ``runners/stream.py``'s streaming pipeline both sit
    on exactly this stack).  The primary --model is the flagship entry;
    every --models spec adds one more, all AOT-warmed before ready."""
    t_entry = time.monotonic()
    from ..models import create_model          # pays the jax import
    from ..serving.batcher import MicroBatcher
    from ..serving.engine import InferenceEngine
    from ..serving.metrics import (ServingMetrics,
                                   install_backend_compile_listener)

    # the probe must see EVERY compile this process pays — including
    # the params-load init jit — so the warm path's zero-backend-compile
    # contract is checked against the whole start, not just the engine
    install_backend_compile_listener()

    # jax's persistent compilation cache: the fallback tier under the
    # AOT executable store — before the first compile
    from ..utils.compile_cache import setup_compile_cache
    setup_compile_cache(cfg.compile_cache_dir)
    t_import = time.monotonic()
    _logger.info("building %s (in_chans=%d, canvas %d², dtype=%s)",
                 cfg.model, cfg.in_chans, cfg.image_size, cfg.dtype)
    model = create_model(cfg.model, num_classes=cfg.num_classes,
                         in_chans=cfg.in_chans)
    variables = _load_model_variables(
        model, cfg.model_path, image_size=cfg.image_size,
        in_chans=cfg.in_chans, use_ema=cfg.use_ema, name=cfg.model)
    metrics = ServingMetrics(throughput_window_s=cfg.throughput_window_s)
    warmstart = None
    if cfg.warmstart_dir:
        from ..serving.warmstart import ExecutableStore
        warmstart = ExecutableStore(cfg.warmstart_dir)
        _logger.info("warm-start executable store: %s", warmstart.root)
    engine = InferenceEngine(
        model, variables, image_size=cfg.image_size, img_num=cfg.img_num,
        buckets=cfg.buckets, metrics=metrics, wire=cfg.wire,
        multi_frame=not cfg.single_frame_only,
        dtype=cfg.dtype, model_id=cfg.model, warmup=False,
        watchdog_timeout_s=cfg.watchdog_timeout_s,
        breaker_threshold=cfg.breaker_threshold,
        breaker_open_s=cfg.breaker_open_s,
        reload_drift_tol=cfg.reload_drift_tol,
        retry_jitter_s=cfg.retry_jitter_s,
        warmstart=warmstart,
        warm_priority=cfg.warm_priority_buckets() or None,
        warm_parallel=cfg.warm_parallel)
    specs = cfg.model_specs()
    for spec in specs:
        in_chans = 3 * spec["img_num"]
        _logger.info("adding model %r: %s (in_chans=%d, canvas %d², "
                     "dtype=%s)", spec["id"], spec["family"], in_chans,
                     spec["size"], spec["dtype"])
        extra = create_model(spec["family"], num_classes=cfg.num_classes,
                             in_chans=in_chans)
        extra_vars = _load_model_variables(
            extra, spec["path"], image_size=spec["size"],
            in_chans=in_chans, use_ema=cfg.use_ema, name=spec["id"])
        engine.add_model(spec["id"], extra, extra_vars,
                         image_size=spec["size"], img_num=spec["img_num"],
                         dtype=spec["dtype"])
    # cold-start stage walls up to here (the engine stamps compile/warm
    # inside warmup; main() stamps spawn/ready around the whole build)
    t_params = time.monotonic()
    metrics.warmup_seconds["import"] = t_import - t_entry
    metrics.warmup_seconds["params_load"] = t_params - t_import
    _logger.info("AOT-warming buckets %s × %d model(s)%s ...",
                 list(cfg.buckets), 1 + len(specs),
                 " (staged)" if cfg.warm_staged else "")
    engine.warmup(staged=cfg.warm_staged)
    if cfg.profile_dir:
        from ..obs.profiler import ProfilerCapture
        os.makedirs(cfg.profile_dir, exist_ok=True)
        engine.profiler = ProfilerCapture(cfg.profile_dir,
                                          num_steps=cfg.profile_capture)
        if not engine.profiler.install():
            _logger.warning("not in the main thread: SIGUSR2 profiler "
                            "trigger unavailable (the PROFILE file works)")
        _logger.info("profiler capture: kill -USR2 %d or touch %s traces "
                     "%d device batches", os.getpid(),
                     os.path.join(cfg.profile_dir, "PROFILE"),
                     cfg.profile_capture)
    if engine.chaos.active:
        _logger.warning("DFD_CHAOS active: %s", sorted(engine.chaos.points))
    cache = None
    if int(cfg.cache_entries) > 0:
        from ..cache import VerdictCache
        cache = VerdictCache(cfg.cache_entries, cfg.cache_ttl_s,
                             near_dup=cfg.cache_near_dup,
                             near_radius=cfg.cache_near_radius,
                             on_expired=metrics.cache_expired_total.inc,
                             on_evicted=metrics.cache_evicted_total.inc)
        # engine.start() hands the cache + fingerprint resolver to the
        # batcher; holding it on the engine also lets a reload commit
        # purge (and count) the entries its fingerprint bump orphaned
        engine.verdict_cache = cache
        _logger.info("verdict cache: %d entries, ttl %.0fs%s",
                     cfg.cache_entries, cfg.cache_ttl_s,
                     (f", near-dup radius {cfg.cache_near_radius}"
                      if cfg.cache_near_dup else ""))
    batcher = MicroBatcher(max_batch=cfg.max_batch_size,
                           deadline_ms=cfg.batch_deadline_ms,
                           max_queue=cfg.max_queue, metrics=metrics,
                           retry_jitter_s=cfg.retry_jitter_s,
                           cache=cache)
    if cfg.reload_dir:
        engine.start_reload_watcher(cfg.reload_dir,
                                    interval_s=cfg.reload_interval_s,
                                    use_ema=cfg.use_ema)
        _logger.info("hot-reload watcher on %s (every %.1fs)",
                     cfg.reload_dir, cfg.reload_interval_s)
    for spec in specs:
        if spec["reload"]:
            engine.start_reload_watcher(spec["reload"],
                                        interval_s=cfg.reload_interval_s,
                                        use_ema=cfg.use_ema,
                                        model_id=spec["id"])
            _logger.info("hot-reload watcher for model %r on %s",
                         spec["id"], spec["reload"])
    return engine, batcher, metrics


def build_server(cfg):
    """Wire model table → engine → batcher → (optional cascade) → HTTP
    server; returns the (not yet started) :class:`ServingServer` with
    engine/batcher attached."""
    from ..serving.http import make_server

    engine, batcher, metrics = build_engine(cfg)
    cascade = None
    if cfg.cascade:
        from ..serving.cascade import CascadeRouter
        cascade = CascadeRouter(
            batcher, metrics, student_id=cfg.cascade,
            flagship_id=engine.default_model_id,
            low=cfg.cascade_low, high=cfg.cascade_high,
            timeout_s=cfg.request_timeout_ms / 1000.0)
        _logger.info("cascade: student %r triages, suspect band "
                     "[%.3f, %.3f] escalates to %r", cfg.cascade,
                     cfg.cascade_low, cfg.cascade_high,
                     engine.default_model_id)
    return make_server(cfg.host, cfg.port, engine, batcher, metrics,
                       request_timeout_s=cfg.request_timeout_ms / 1000.0,
                       cascade=cascade)


def main(argv: Optional[Sequence[str]] = None) -> None:
    t_main = time.time()
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s: %(message)s")
    # many handler threads + the engine share few cores; the default 5 ms
    # GIL switch interval convoys tail latency badly under load
    sys.setswitchinterval(0.002)
    from ..config import ServeConfig
    cfg = ServeConfig.from_args(argv)
    if cfg.single_thread_xla:
        # must land before the first jax import (build_server's) initializes
        # the backend; see ServeConfig.single_thread_xla
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_cpu_multi_thread_eigen" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_cpu_multi_thread_eigen=false").strip()
    server = build_server(cfg)
    # spawn/ready stage walls: a parent (fleet controller, bench) stamps
    # DFD_SPAWN_T at fork so the breakdown starts at the true spawn; a
    # bare launch starts at main() entry (spawn stage reads 0)
    try:
        spawn_t = float(os.environ.get("DFD_SPAWN_T", "") or t_main)
    except ValueError:
        spawn_t = t_main
    m = server.engine.metrics
    m.warmup_seconds["spawn"] = max(0.0, t_main - spawn_t)
    m.warmup_seconds["ready"] = max(0.0, time.time() - spawn_t)
    server.engine.start(server.batcher)

    stop = threading.Event()

    def _sig(signum, frame):
        _logger.info("signal %d: shutting down", signum)
        stop.set()

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    host, port = server.server_address[:2]
    _logger.info("serving on http://%s:%d (POST /score, GET /healthz "
                 "/readyz /metrics)", host, port)
    t = threading.Thread(target=server.serve_forever,
                         kwargs={"poll_interval": 0.1}, daemon=True)
    t.start()
    try:
        while not stop.is_set():
            stop.wait(0.5)
    finally:
        server.shutdown()
        server.engine.stop()
        server.batcher.close()
        server.server_close()
        _logger.info("bye")


if __name__ == "__main__":
    main(sys.argv[1:])
