"""Training driver: one cell = one configuration under one pool mix.

The window drives ``train/trainer.py:train_one_epoch`` over the jitted step
that ``make_train_step`` returns and the real ``DeviceLoader``, built with
``runners/train.py``'s own builders from the configuration file's flags.
The data is the benchmark's seeded pool and the weights are the benchmark's
seeded weights; everything between them is the program's.

Set-up builds ONE step with ONE state, drives it through its first epoch
(that compiles, warms the loader and taps the first three steps for the
comparison) and hands the same objects to the window.  The window runs whole
epochs until ``--seconds`` have passed.  Once it has closed, the peak memory
has been read and the state is freed, the plain reference (the module the
configuration names, ``benchmark/reference/``) rebuilds the three batches
from the pool's rows, follows the three tapped steps and ``correct`` is
decided.  Nothing here names a model family or an optimizer.
"""

from __future__ import annotations

import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark.lib import manifest as M

CHECK_STEPS = 3


# ---------------------------------------------------------------------------
# taps: benchmark-owned pass-throughs around the host loader and the step
# ---------------------------------------------------------------------------

class _PassThrough:
    """Attribute access falls through to the wrapped object, so the program
    sees its own loader or collate."""

    _own = ()

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __setattr__(self, name, value):
        if name.startswith("_") or name in self._own:
            object.__setattr__(self, name, value)
        else:
            setattr(self._inner, name, value)


class HostTap(_PassThrough):
    """Wraps the program's host loader: yields what it yields, and keeps a
    copy of the first batches of epoch 0 (the feed of the tapped steps)."""

    _own = ("batches",)

    def __init__(self, inner, keep: int):
        self._inner, self._keep = inner, keep
        self.batches: List[Any] = []

    def __len__(self):
        return len(self._inner)

    def __iter__(self):
        for item in self._inner:
            if len(self.batches) < self._keep and self._inner.epoch == 0:
                self.batches.append((np.array(item[0]), np.array(item[1])))
            yield item


class MixTap(_PassThrough):
    """Wraps the program's collate mixup: returns what it returns, and keeps
    of its first calls the stacked rows it was given and the lambda it drew
    (read from a copy of the generator it was handed, so its own draw is
    untouched).  ``fault`` plants a wrong blend for the tests."""

    _own = ("rows", "lams", "fault")

    def __init__(self, inner, keep: int, fault: Optional[str] = None):
        self._inner, self._keep = inner, keep
        self.rows: List[np.ndarray] = []
        self.lams: List[float] = []
        self.fault = fault

    def __call__(self, images, targets, rng):
        if len(self.rows) < self._keep:
            import copy
            a = float(self._inner.mixup_alpha)
            self.lams.append(float(copy.deepcopy(rng).beta(a, a))
                             if self._inner.mixup_enabled else 1.0)
            self.rows.append(np.array(images))
        if self.fault == "blend_not_mirrored":
            images = np.roll(images, 1, axis=0)
        return self._inner(images, targets, rng)


class StepTap:
    """The one callable that set-up and the window both hand to
    ``train_one_epoch``.  For the first three calls it keeps what the
    comparison needs (host copies: parameters before, optimizer state after
    step 1, parameters after step 3, each loss); after that it is a plain
    call, on whose way the planned trace sessions start and stop.  ``fault``
    plants a broken step for the tests."""

    def __init__(self, step: Callable, fault: Optional[str] = None):
        self.step, self.fault = step, fault
        self.calls = 0
        self.losses: List[float] = []
        self.params0 = self.stats0 = self.opt1 = self.params3 = None
        self.arg_specs = None
        self.sessions: List[Dict[str, Any]] = []   # planned traces, in order
        self.traced: Optional[Dict[str, float]] = None
        self._t_trace = None

    def plan_trace(self, first_call: int, steps: int, trace_dir: str,
                   on_start: Optional[Callable[[], None]] = None,
                   keep: bool = True) -> None:
        """Trace ``steps`` launches in mid-epoch, from the call numbered
        ``first_call``: the loop is in its steady state on both sides, so
        the traced span holds neither a prefetch queue that drains nor an
        epoch's end.  A session that is not kept primes the profiler (see
        ``run``): its files are deleted when it stops."""
        self.sessions.append({"first": first_call, "last": first_call + steps,
                              "dir": trace_dir, "on_start": on_start,
                              "keep": keep})

    def _trace_edge(self) -> None:
        from benchmark.lib import trace as TR
        plan = self.sessions[0]
        if self.calls == plan["first"]:
            if plan["on_start"]:
                plan["on_start"]()
            TR.start(plan["dir"])
            self._t_trace = time.monotonic()
        elif self.calls == plan["last"]:
            self.stop_trace()

    def stop_trace(self) -> None:
        if self._t_trace is None:
            return
        import jax
        t = time.monotonic()
        jax.profiler.stop_trace()
        plan = self.sessions.pop(0)
        info = {"host_wall_s": t - self._t_trace,
                "stop_s": time.monotonic() - t}
        if plan["keep"]:
            self.traced = info
        else:
            shutil.rmtree(plan["dir"], ignore_errors=True)
        self._t_trace = None

    def __call__(self, state, x, y, rng):
        import jax
        if self.calls >= CHECK_STEPS:
            if self.sessions:
                self._trace_edge()
            self.calls += 1
            return self._call(state, x, y, rng)
        if self.calls == 0:
            self.params0 = jax.device_get(state.params)
            self.stats0 = jax.device_get(state.batch_stats)
            self.arg_specs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding),
                (state, x, y, rng))
        state, metrics = self._call(state, x, y, rng)
        self.calls += 1
        self.losses.append(float(metrics["loss"]))
        if self.calls == 1:
            self.opt1 = jax.device_get(state.opt_state)
        if self.calls == CHECK_STEPS:
            self.params3 = jax.device_get(state.params)
        return state, metrics

    def _call(self, state, x, y, rng):
        if self.fault == "state_unchanged":
            import jax.numpy as jnp
            return state, {"loss": jnp.float32(0.69), "prec1": jnp.float32(50)}
        if self.fault == "half_batch":
            import jax.numpy as jnp
            h = max(1, x.shape[0] // 2)
            reps = -(-x.shape[0] // h)
            x = jnp.concatenate([x[:h]] * reps)[:x.shape[0]]
            y = jnp.concatenate([y[:h]] * reps)[:y.shape[0]]
        return self.step(state, x, y, rng)


# ---------------------------------------------------------------------------
# building the program's objects
# ---------------------------------------------------------------------------

def setup_cache(cache_dir: str) -> str:
    """The persistent compile cache, at a fixed path inside the checkout."""
    import jax
    path = os.path.join(cache_dir, "jax")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_compilation_cache_max_size", 4 * 1024 ** 3)
    return path


def require_chips(chips: int) -> Dict[str, Any]:
    """Exit without a result where JAX finds no accelerator or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark: needs {chips} TPU chip(s), found "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        raise SystemExit(3)
    peaks = M.load_json(os.path.join(M.BENCH, "lib", "peaks.json"))
    kind = devs[0].device_kind
    if kind not in peaks:
        print(f"benchmark: device_kind {kind!r} is not in "
              "benchmark/lib/peaks.json", file=sys.stderr)
        raise SystemExit(4)
    return peaks[kind]


class Built:
    """The program's objects for one configuration, reusable over seeds."""

    def __init__(self, cell: M.Cell, out_dir: str):
        import jax
        from deepfake_detection_tpu.config import TrainConfig
        from deepfake_detection_tpu.data import resolve_data_config
        from deepfake_detection_tpu.losses import create_loss_fn
        from deepfake_detection_tpu.optim import create_optimizer
        from deepfake_detection_tpu.parallel import (data_axis_name,
                                                     make_train_mesh)
        from deepfake_detection_tpu.runners import train as T
        from deepfake_detection_tpu.scheduler import create_scheduler
        self.cell, self.out_dir = cell, out_dir
        config = cell.config
        os.makedirs(out_dir, exist_ok=True)
        self.cfg = cfg = TrainConfig.from_args(
            list(config["train_flags"]) + ["--output", out_dir])
        self.mesh = make_train_mesh()
        self.n_dev = int(self.mesh.size)
        self.batch_axis = data_axis_name(self.mesh)
        self.dp = int(self.mesh.shape.get(self.batch_axis, self.n_dev))
        self.data_config = resolve_data_config(cfg.to_dict(), verbose=False)
        self.input_size = tuple(self.data_config["input_size"])
        assert list(self.input_size) == list(config["input_size"]), \
            (self.input_size, config["input_size"])
        self.model = T.build_model(cfg, self.input_size[0])
        self.lr = cfg.resolved_lr(world_size=self.dp * cfg.grad_accum)
        self.tx = create_optimizer(cfg, learning_rate=self.lr)
        self.lr_scheduler, _ = create_scheduler(cfg, base_lr=self.lr)
        self.loss_fn = create_loss_fn(cfg)
        self.global_batch = cfg.batch_size * self.dp * cfg.grad_accum
        self.train_step = None

    def state_for(self, variables):
        """A placed TrainState from the benchmark's weights (consumed)."""
        from deepfake_detection_tpu.parallel import (place_train_state,
                                                     train_state_shardings)
        from deepfake_detection_tpu.train import (create_train_state,
                                                  make_train_step)
        cfg = self.cfg
        state = create_train_state(variables, self.tx, with_ema=cfg.model_ema)
        shardings = train_state_shardings(state, self.mesh, fsdp=cfg.fsdp,
                                          axis=self.batch_axis)
        state = place_train_state(state, shardings)
        if self.train_step is None:
            bn_mode = "global" if (cfg.sync_bn or cfg.tp_size > 1) \
                else "local"
            self.train_step = make_train_step(
                self.model, self.tx, self.loss_fn, mesh=self.mesh,
                axis=self.batch_axis, bn_mode=bn_mode,
                ema_decay=cfg.model_ema_decay if cfg.model_ema else 0.0,
                clip_grad=cfg.clip_grad, grad_accum=cfg.grad_accum,
                nonfinite_guard=cfg.guard_nonfinite == "skip",
                state_shardings=shardings)
        return state

    def loader_for(self, dataset, seed: int, keep: int,
                   fault: Optional[str] = None):
        """The real DeviceLoader over the pool, as ``runners/train.py``
        builds it, with the taps spliced in: one around its collate mixup
        (where the recipe has one) and one under the device loader.
        Returns (loader, host tap, mix tap or None)."""
        from deepfake_detection_tpu.data import (FastCollateMixup,
                                                 create_deepfake_loader_v3)
        from deepfake_detection_tpu.parallel import batch_sharding
        from deepfake_detection_tpu.runners import train as T
        cfg = self.cfg
        mix = FastCollateMixup(cfg.mixup, cfg.smoothing, cfg.num_classes) \
            if cfg.mixup > 0 else None
        loader = create_deepfake_loader_v3(
            dataset, self.input_size, self.global_batch, is_training=True,
            re_prob=cfg.reprob, re_mode=cfg.remode, re_count=cfg.recount,
            re_split=cfg.resplit, re_max=cfg.remax,
            color_jitter=cfg.color_jitter, num_aug_splits=cfg.aug_splits,
            collate_mixup=mix, flicker=cfg.flicker,
            rotate_range=cfg.rotate_range, blur_radius=1,
            blur_prob=cfg.blur_prob,
            device_color_jitter=not cfg.host_color_jitter,
            fused_geom=not cfg.host_geom,
            augment_device=cfg.augment_device == "on",
            mean=self.data_config["mean"], std=self.data_config["std"],
            num_workers=cfg.workers, seed=seed,
            dtype=T._dtype(cfg.compute_dtype),
            sharding=batch_sharding(self.mesh), distributed=False,
            prefetch_depth=cfg.prefetch_depth,
            loader_backend=cfg.loader_backend, ring_depth=cfg.ring_depth,
            worker_heartbeat=cfg.worker_heartbeat, stem_s2d=cfg.stem_s2d)
        host = loader.loader
        mix_tap = None
        if host.collate_mixup is not None:
            mix_tap = host.collate_mixup = MixTap(host.collate_mixup, keep,
                                                  fault)
        tap = HostTap(host, keep)
        loader.loader = tap
        return loader, tap, mix_tap

    def rng_for(self, seed: int):
        import jax
        from deepfake_detection_tpu.parallel import (own_and_place,
                                                     replicated_sharding)
        from benchmark.lib.weights import seed_key
        key = jax.random.fold_in(seed_key(seed), 0x7472)
        return own_and_place(np.asarray(key), replicated_sharding(self.mesh))


def make_inputs(cell: M.Cell, seed: int, batch: int):
    """Pool dataset and seeded weights of one run."""
    from benchmark.lib.pool import PoolDataset, make_pool
    from benchmark.lib.weights import variables_for
    config, mix = cell.config, cell.traffic
    c, h, w = config["input_size"]
    n = max(batch, min(int(mix["pool_samples_max"]),
                       int(mix["pool_bytes"]) // (c * h * w)))
    n -= n % batch
    pool, labels = make_pool(seed, n, h, w, c)
    steps = int(config["train"]["steps_per_epoch"])
    dataset = PoolDataset(pool, labels, length=steps * batch)
    variables, spec = variables_for(config, seed)
    return dataset, variables, spec


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def _leaf_norms(tree) -> List[float]:
    import jax
    return [float(np.sqrt(np.sum(np.square(np.asarray(a, np.float64)))))
            for a in jax.tree.leaves(tree)]


def reference_batches(config, spec, dataset, host_tap: HostTap,
                      mix_tap: Optional[MixTap]):
    """The reference's own batches for the tapped steps, rebuilt from the
    pool: each row of a batch is found in the pool by its content (before
    the blend, where the recipe mixes), then stacked, blended with the
    lambda the program drew and given its smoothed target by the plain
    collate.  Returns (batches, numbers): ``batch_gap`` is the widest
    distance in uint8 codes between a batch the program fed and the
    reference's, ``target_gap`` the same for the targets.  A row that is no
    row of the pool reads 255, and the program's batch stands in so that the
    other numbers can still be read."""
    from benchmark.reference import collate as C
    ref = config["reference"]
    sm, k = float(ref["smoothing"]), int(spec["num_classes"])
    index = C.pool_index(dataset.pool)
    batches, batch_gap, target_gap = [], 0.0, 0.0
    for i, (images, targets) in enumerate(host_tap.batches):
        rows, lam = (mix_tap.rows[i], mix_tap.lams[i]) \
            if mix_tap is not None else (images, 1.0)
        idx = C.find_rows(index, rows)
        if min(idx) < 0:
            batch_gap = 255.0
            if np.ndim(targets) == 1:
                targets = C.soft_targets(targets, 1.0, sm, k)
            batches.append((images, np.asarray(targets, np.float32)))
            continue
        r_images, r_targets = C.rebuild_batch(
            dataset.pool, dataset.labels, idx, lam, sm, k)
        if np.ndim(targets) == 1:       # integer labels: the loss smooths
            targets = C.soft_targets(targets, 1.0, sm, k)
        batch_gap = max(batch_gap, float(np.max(np.abs(
            images.astype(np.int16) - r_images.astype(np.int16)))))
        target_gap = max(target_gap, float(np.max(np.abs(
            np.asarray(targets, np.float64) - r_targets))))
        batches.append((r_images, r_targets))
    return batches, {"batch_gap": batch_gap, "target_gap": target_gap}


def reference_first_steps(config, spec, params0, stats0, batches, seed: int,
                          quant=None):
    """The plain reference through the tapped steps: losses, the first
    gradient as its optimizer got it, and the parameters' change."""
    import jax
    import jax.numpy as jnp
    from benchmark import reference
    R, O = reference.model(config), reference.optimizer(config)
    ref = config["reference"]
    opt_kw = {k: float(v) for k, v in ref["optimizer"].items()
              if k != "name"}
    params = jax.tree.map(jnp.asarray, params0)
    stats = jax.tree.map(jnp.asarray, stats0)
    opt = O.init(params)
    losses, g1, g1_tree = [], None, None
    for i, (images, targets) in enumerate(batches):
        x = R.prologue(jnp.asarray(images), i, ref["prologue"], seed)
        loss, grads, stats, _ = R.loss_and_grads(
            params, stats, x, jnp.asarray(targets, jnp.float32), spec,
            quant=quant)
        params, opt, g = O.update(params, grads, opt, **opt_kw)
        losses.append(float(loss))
        if i == 0:
            g1_tree = jax.tree.map(lambda a: np.asarray(a, np.float64), g)
            g1 = _leaf_norms(g1_tree)
    delta = _leaf_norms(_tree_sub(jax.device_get(params), params0))
    return {"losses": losses, "grad1": g1, "delta": delta,
            "grad1_tree": g1_tree}


def program_numbers(tap: StepTap, config):
    """The same three readings from what the tap kept of the program.  The
    first gradient is worked out of the program's optimizer state after one
    step by the optimizer's plain reference, with the configuration's
    numbers."""
    from benchmark import reference
    opt = config["reference"]["optimizer"]
    g1 = reference.optimizer(config).program_first_gradient(
        tap.opt1, **{k: float(v) for k, v in opt.items() if k != "name"})
    return {"losses": list(tap.losses), "grad1": _leaf_norms(g1),
            "delta": _leaf_norms(_tree_sub(tap.params3, tap.params0)),
            "grad1_tree": g1}


def _tree_sub(a, b):
    import jax
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def leaf_gaps(prog: List[float], ref: List[float]) -> List[float]:
    med = float(np.median(ref))
    return [abs(p - r) / max(r, med) for p, r in zip(prog, ref)]


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers that may be held to limits (a configuration's ``limits``
    names those that are)."""
    med = float(np.median(ref["grad1"]))
    moved = [g >= 1e-3 * med for g in ref["grad1"]]   # the leaf rule
    dgaps = [g for g, m in zip(leaf_gaps(prog["delta"], ref["delta"]), moved)
             if m]
    # relative error of the first gradient, leaf by leaf (the norm of the
    # difference: rounding noise barely moves a norm, so a lower precision
    # hides from the gaps of norms above and shows here)
    errs = [n / max(r, med) for n, r in zip(_leaf_norms(_tree_sub(
        prog["grad1_tree"], ref["grad1_tree"])), ref["grad1"])]
    return {
        "grad1_err_median": float(np.median(errs)),
        "grad1_err_p10": float(np.percentile(errs, 10)),
        "grad1_err_p05": float(np.percentile(errs, 5)),
        "grad1_err_p02": float(np.percentile(errs, 2)),
        "grad1_err_p25": float(np.percentile(errs, 25)),
        "loss_gap": max(abs(p - r) / abs(r)
                        for p, r in zip(prog["losses"], ref["losses"])),
        "loss1_gap": abs(prog["losses"][0] - ref["losses"][0])
        / abs(ref["losses"][0]),
        "grad1_gap": max(leaf_gaps(prog["grad1"], ref["grad1"])),
        "grad1_median_gap": float(np.median(
            leaf_gaps(prog["grad1"], ref["grad1"]))),
        "delta_gap": max(dgaps),
        "delta_median_gap": float(np.median(dgaps)),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float],
          every_limit: bool = False):
    """(correct, {name: value and limit}).  With ``every_limit`` a limit
    whose number is missing fails (a run has them all; a control read
    through ``compare`` alone has no batch numbers)."""
    compared = {k: {"value": float(v), "limit": float(limits[k])}
                for k, v in numbers.items() if k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in compared.values()) and bool(compared)
    if every_limit and set(limits) - set(compared):
        ok = False
    return ok, compared


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(cell: M.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, need_chip: bool = True,
        fault: Optional[str] = None) -> Dict[str, Any]:
    import jax
    from deepfake_detection_tpu.obs import (EventLog, TrainTelemetry,
                                            loader_collector)
    from deepfake_detection_tpu.train import train_one_epoch
    from benchmark.lib import flops as F
    from benchmark.lib import trace as TR

    peak = require_chips(cell.chips) if need_chip else None
    setup_cache(cell.cache_dir)
    out_dir = os.path.join(cell.cache_dir, "out")
    shutil.rmtree(out_dir, ignore_errors=True)
    built = Built(cell, out_dir)
    cfg = built.cfg
    batch = built.global_batch
    dataset, variables, spec = make_inputs(cell, seed, batch)
    state = built.state_for(variables)
    loader_seed = seed % (2 ** 31 - 1)
    step_fault = fault if fault in ("state_unchanged", "half_batch") else None
    loader, host_tap, mix_tap = built.loader_for(
        dataset, loader_seed, CHECK_STEPS,
        fault=None if step_fault else fault)
    rng = built.rng_for(seed)
    step = StepTap(built.train_step, fault=step_fault)
    telemetry = TrainTelemetry(event_log=EventLog(
        os.path.join(out_dir, "telemetry.jsonl")))
    telemetry.register_collector(loader_collector(loader))

    def epoch(e, state):
        loader.set_epoch(e)
        return train_one_epoch(
            e, step, state, loader, cfg, jax.random.fold_in(rng, e),
            lr_scheduler=built.lr_scheduler, saver=None, output_dir="",
            world_size=built.n_dev, telemetry=telemetry)

    # ---- set-up: compile, warm the loader, tap the first three steps ----
    trace_dir = os.path.join(cell.cache_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    steps_per_epoch = len(loader)
    snaps = {}
    if trace:
        # A process's first trace of a device-bound loop stalls every launch
        # (B4: 1.8 s a launch, PERF.md section 6); its second does not.  So
        # set-up primes the profiler with a few launches that are thrown
        # away, and the window's trace is the second.  Counters are read
        # from the window's start to that trace's start: stopping the
        # profiler stalls the loop, and that is no input wait.
        mix = cell.traffic
        at = int(mix["trace_from_step"])
        step.plan_trace(at, int(mix["trace_prime_steps"]),
                        trace_dir + "_prime", keep=False)
        step.plan_trace(int(mix["trace_epoch"]) * steps_per_epoch + at,
                        int(mix["trace_steps"]), trace_dir,
                        lambda: snaps.update(
                            at_trace=telemetry.snapshot()["counters"]))
    state, _ = epoch(0, state)
    step.stop_trace()
    setup_s = time.time() - t_start

    # ---- the window ----
    snap0 = telemetry.snapshot()["counters"]
    e, t0 = 0, time.monotonic()
    while time.monotonic() - t0 < seconds:
        e += 1
        state, last = epoch(e, state)
    step.stop_trace()
    window_s = time.monotonic() - t0
    snap1 = snaps.get("at_trace") or telemetry.snapshot()["counters"]
    rows = e * steps_per_epoch * batch
    attempted, failed = e * steps_per_epoch, int(last.get("nonfinite", 0))

    # ---- close: memory, then free the program's state ----
    mem = jax.devices()[0].memory_stats() or {}
    # buffers at their peak plus the region XLA reserves for the compiled
    # programs' temporaries (peak_bytes_in_use alone leaves those out)
    peak_bytes = int(mem.get("peak_bytes_in_use", 0)) + \
        int(mem.get("peak_bytes_reserved", 0))
    loader.close()
    telemetry.close()
    del state, loader

    # ---- correct: the reference follows the tapped steps ----
    t_ref = time.monotonic()
    prog = program_numbers(step, cell.config)
    batches, numbers = reference_batches(cell.config, spec, dataset,
                                         host_tap, mix_tap)
    ref = reference_first_steps(cell.config, spec, step.params0, step.stats0,
                                batches, loader_seed)
    numbers.update(compare(prog, ref))
    ok, compared = judge(numbers, cell.config["reference"]["limits"],
                         every_limit=True)
    reference_s = time.monotonic() - t_ref

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": built.n_dev, "memory_peak_bytes": peak_bytes}
    result: Dict[str, Any] = {
        "correct": bool(ok), "attempted": attempted, "failed": failed,
        "metrics": {}, "device": device}
    unit = {m["name"]: m["unit"] for m in cell.end_to_end()}
    if not trace:
        e2e = {"train_clips_per_s": rows / window_s, "setup_s": setup_s}
        result["metrics"] = {k: {"value": float(v), "unit": unit[k]}
                             for k, v in e2e.items() if k in unit}
    else:
        red, traced = {}, None
        if step.traced and step.arg_specs is not None:
            # the trace names HLO instructions; their framework paths are
            # in the compiled step's own text (a cache hit, no new compile)
            paths = TR.hlo_paths(built.train_step.lower(
                *step.arg_specs).compile().as_text())
            red = TR.reduce_trace(trace_dir, paths,
                                  cell.config.get("trace_groups", ()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red and red.get("steps"):
            # rows and seconds of the steady span, on the device's clock
            traced = {"rows": red["steps"] * batch, "wall_s": red["window_s"]}
        evidence = {
            "peak": peak, "trace": red, "traced": traced,
            "counters0": snap0, "counters1": snap1,
            "flop_counts": F.forward_counts(cell.config), "mode": "train",
            "chips": built.n_dev}
        result["metrics"] = M.read_per_layer(cell, evidence)
        if red:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {
                "device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"],
                "by_group": sorted(([k, v] for k, v in
                                    red["by_group"].items()),
                                   key=lambda kv: -kv[1]),
                "other_top": red["other_top"]}
            result["traced"] = dict(step.traced, steps=red["steps"],
                                    step_module=red["step_module"])
    result["window"] = {"seconds": window_s, "epochs": e, "rows": rows,
                        "rows_per_s": rows / window_s, "setup_s": setup_s,
                        "steps_per_epoch": steps_per_epoch, "batch": batch,
                        "reference_s": reference_s}
    result["numbers"] = {k: float(v) for k, v in numbers.items()}
    result["compared"] = compared
    return result
