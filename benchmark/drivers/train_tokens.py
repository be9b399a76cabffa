"""Training driver for the sequence configurations: one cell = one
configuration under one pool-of-documents mix.

The same set-up / window / close / ``correct`` sequence as
``drivers/train.py``, whose taps, comparison and judge it imports: set-up
builds ONE step with ONE state through ``runners/train.py``'s own builders,
drives it through its first epoch (that compiles, warms the loader and taps
the first three steps) and hands the same objects to the window, which runs
whole epochs of ``train_one_epoch`` over the program's token loader until
``--seconds`` have passed.  The data is the benchmark's seeded pool of
documents (ids by a Zipf law over the vocabulary rows held) and the weights
are the benchmark's seeded weights.  Once the window has closed and the
state is freed, the plain reference rebuilds the three tapped batches from
the pool's rows (ids by content, targets by its own shift), follows the
three steps and ``correct`` is decided.

The planted faults (``fault=``, for the tests and the calibration): a state
left unchanged; half the batch left out (a batch is one row here, so half of
its positions lose their targets); the window ignored (the window layer
attends to everything before it); the memory taken from the wrong layer (the
GMUs gate the first Mamba layer's scan, not the producer's).
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import time
from typing import Any, Dict, Optional

import numpy as np

from benchmark.drivers.train import (CHECK_STEPS, Built, HostTap, StepTap,
                                     _leaf_norms, _tree_sub, compare, judge,
                                     program_numbers, require_chips,
                                     setup_cache)
from benchmark.lib import manifest as M

STEP_FAULTS = ("state_unchanged", "half_batch")
MODEL_FAULTS = ("window_ignored", "memory_wrong_layer")


class TokenStepTap(StepTap):
    """``StepTap`` whose half-batch fault fits a batch of one document."""

    def _call(self, state, x, y, rng):
        if self.fault == "half_batch":
            import jax.numpy as jnp
            keep = jnp.arange(y.shape[1]) < y.shape[1] // 2
            return self.step(state, x, jnp.where(keep[None, :], y, -1), rng)
        return super()._call(state, x, y, rng)


def faulty_model(model, fault: Optional[str]):
    """The program's model with one of the model faults planted."""
    if fault == "window_ignored":
        return model.clone(window=1 << 30)
    if fault != "memory_wrong_layer":
        return model
    from deepfake_detection_tpu.models import phi4flash as P

    class WrongMemory(type(model)):
        def hidden(self, ids, training: bool = False):
            x = self.embed(ids)
            memory = kv = None
            for kind, layer in zip(self.schedule, self.layers):
                mem = memory if kind == P.GMU else kv if kind == P.CROSS \
                    else ()
                x, out = layer(x, training, mem)
                if kind == P.MAMBA and memory is None:
                    memory = out               # the FIRST Mamba layer's
                elif kind == P.FULL:
                    kv = out
            return self.final_ln(x)

    return WrongMemory(**{f.name: getattr(model, f.name)
                          for f in dataclasses.fields(model)
                          if f.init and f.name not in ("parent", "name")})


class PoolOfDocuments:
    """A dataset over the pool with the interface the program's token
    loader uses (``data/tokens.py``): ``__getitem__(index, rng)`` gives
    (ids, the ids shifted by one).  ``length`` may pass the pool's size;
    indices wrap."""

    sample_dtype = np.int32

    def __init__(self, pool: np.ndarray, length: int):
        self.pool, self.length = pool, int(length)

    def set_epoch(self, epoch: int) -> None:
        pass

    def set_transform(self, transform) -> None:
        pass

    def __len__(self) -> int:
        return self.length

    def __getitem__(self, index: int, rng=None):
        ids = self.pool[int(index) % len(self.pool)]
        return ids, shift(ids)


def shift(ids: np.ndarray) -> np.ndarray:
    """Next-token targets of rows of ids: -1 where nothing follows."""
    return np.concatenate(
        [ids[..., 1:], np.full(ids.shape[:-1] + (1,), -1, ids.dtype)], -1)


def make_pool(seed: int, rows: int, seq_len: int, vocab_rows: int,
              zipf_s: float) -> np.ndarray:
    """(rows, seq_len) int32 ids, P(id = k) ~ 1 / (k + 1)^s, the same for
    the same seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x646f6373]))
    p = 1.0 / np.arange(1, vocab_rows + 1, dtype=np.float64) ** zipf_s
    cdf = np.cumsum(p / p.sum())
    ids = np.searchsorted(cdf, rng.random((rows, seq_len)), side="right")
    return np.minimum(ids, vocab_rows - 1).astype(np.int32)


class TokenBuilt(Built):
    """The program's objects for one sequence configuration."""

    def __init__(self, cell: M.Cell, out_dir: str,
                 fault: Optional[str] = None):
        from deepfake_detection_tpu.config import TrainConfig
        from deepfake_detection_tpu.losses import create_loss_fn
        from deepfake_detection_tpu.optim import create_optimizer
        from deepfake_detection_tpu.parallel import (data_axis_name,
                                                     make_train_mesh)
        from deepfake_detection_tpu.runners import train as T
        from deepfake_detection_tpu.scheduler import create_scheduler
        self.cell, self.out_dir = cell, out_dir
        os.makedirs(out_dir, exist_ok=True)
        self.cfg = cfg = TrainConfig.from_args(
            list(cell.config["train_flags"]) + ["--output", out_dir])
        assert cfg.seq_len == int(cell.config["train"]["seq_len"])
        self.mesh = make_train_mesh()
        self.n_dev = int(self.mesh.size)
        self.batch_axis = data_axis_name(self.mesh)
        self.dp = int(self.mesh.shape.get(self.batch_axis, self.n_dev))
        self.model = faulty_model(T.build_model(cfg, 0), fault)
        assert self.model.vocab_rows == int(cell.config["vocab_size"])
        self.lr = cfg.resolved_lr(world_size=self.dp * cfg.grad_accum)
        self.tx = create_optimizer(cfg, learning_rate=self.lr)
        self.lr_scheduler, _ = create_scheduler(cfg, base_lr=self.lr)
        self.loss_fn = create_loss_fn(cfg)
        self.global_batch = cfg.batch_size * self.dp * cfg.grad_accum
        self.train_step = None

    def loader_for(self, dataset, seed: int, keep: int):
        """The program's token loader over the pool, with the tap spliced
        in under the device loader.  Returns (loader, host tap)."""
        from deepfake_detection_tpu.data import create_token_loader
        from deepfake_detection_tpu.parallel import batch_sharding
        cfg = self.cfg
        loader = create_token_loader(
            dataset, self.global_batch, is_training=True,
            num_workers=cfg.workers, seed=seed,
            sharding=batch_sharding(self.mesh), distributed=False,
            prefetch_depth=cfg.prefetch_depth)
        tap = HostTap(loader.loader, keep)
        loader.loader = tap
        return loader, tap


def make_inputs(cell: M.Cell, seed: int, batch: int):
    """Pool dataset and seeded weights of one run."""
    from benchmark.lib.weights import variables_for
    config, mix = cell.config, cell.traffic
    pool = make_pool(seed, int(mix["pool_rows"]),
                     int(config["train"]["seq_len"]),
                     int(config["vocab_size"]), float(mix["zipf_s"]))
    steps = int(config["train"]["steps_per_epoch"])
    variables, spec = variables_for(config, seed)
    return PoolOfDocuments(pool, steps * batch), variables, spec


def reference_batches(dataset: PoolOfDocuments, host_tap: HostTap):
    """The reference's own batches for the tapped steps: each row the
    program fed is found in the pool by its content, and the batch is those
    rows of the pool with the reference's own shifted targets.
    ``batch_gap`` is the widest distance between an id the program fed and
    the reference's (a row that is no row of the pool reads the whole
    vocabulary), ``target_gap`` the same for the targets."""
    from benchmark.reference import collate as C
    index = C.pool_index(dataset.pool)
    batches, batch_gap, target_gap = [], 0.0, 0.0
    for ids, targets in host_tap.batches:
        idx = C.find_rows(index, ids)
        if min(idx) < 0:
            batch_gap = float(np.iinfo(np.int32).max)
            batches.append((ids, np.asarray(targets)))
            continue
        r_ids = dataset.pool[np.asarray(idx)]
        r_targets = shift(r_ids)
        batch_gap = max(batch_gap, float(np.max(np.abs(
            ids.astype(np.int64) - r_ids))))
        target_gap = max(target_gap, float(np.max(np.abs(
            np.asarray(targets, np.int64) - r_targets))))
        batches.append((r_ids, r_targets))
    return batches, {"batch_gap": batch_gap, "target_gap": target_gap}


def reference_first_steps(config, spec, params0, batches, quant=None):
    """The plain reference through the tapped steps: losses, the first
    gradient as its optimizer got it, and the parameters' change."""
    import jax
    import jax.numpy as jnp
    from benchmark import reference
    R, O = reference.model(config), reference.optimizer(config)
    opt_kw = {k: float(v) for k, v in
              config["reference"]["optimizer"].items() if k != "name"}
    params = jax.tree.map(jnp.asarray, params0)
    opt = O.init(params)
    losses, g1_tree = [], None
    for i, (ids, targets) in enumerate(batches):
        loss, grads, _, _ = R.loss_and_grads(
            params, {}, jnp.asarray(ids), jnp.asarray(targets), spec,
            quant=quant)
        params, opt, g = O.update(params, grads, opt, **opt_kw)
        losses.append(float(loss))
        if i == 0:
            g1_tree = jax.tree.map(lambda a: np.asarray(a, np.float32), g)
        del grads, g
    delta = _leaf_norms(_tree_sub(jax.device_get(params), params0))
    return {"losses": losses, "grad1": _leaf_norms(g1_tree), "delta": delta,
            "grad1_tree": g1_tree}


def run(cell: M.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, need_chip: bool = True,
        fault: Optional[str] = None) -> Dict[str, Any]:
    import jax
    from deepfake_detection_tpu.obs import (EventLog, TrainTelemetry,
                                            loader_collector)
    from deepfake_detection_tpu.train import train_one_epoch
    from benchmark.lib import flops_seq as F
    from benchmark.lib import trace as TR

    peak = require_chips(cell.chips) if need_chip else None
    setup_cache(cell.cache_dir)
    out_dir = os.path.join(cell.cache_dir, "out")
    shutil.rmtree(out_dir, ignore_errors=True)
    built = TokenBuilt(cell, out_dir,
                       fault if fault in MODEL_FAULTS else None)
    cfg = built.cfg
    batch = built.global_batch
    dataset, variables, spec = make_inputs(cell, seed, batch)
    state = built.state_for(variables)
    loader_seed = seed % (2 ** 31 - 1)
    loader, host_tap = built.loader_for(dataset, loader_seed, CHECK_STEPS)
    rng = built.rng_for(seed)
    step = TokenStepTap(built.train_step,
                        fault=fault if fault in STEP_FAULTS else None)
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
        # primed as in drivers/train.py: a process's first trace of a
        # device-bound loop stalls every launch, its second does not
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
    snap_end = telemetry.snapshot()["counters"]
    snap1 = snaps.get("at_trace") or snap_end
    rows = e * steps_per_epoch * batch
    attempted, failed = e * steps_per_epoch, int(last.get("nonfinite", 0))

    # ---- close: memory, then free the program's state ----
    mem = jax.devices()[0].memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0)) + \
        int(mem.get("peak_bytes_reserved", 0))
    loader.close()
    telemetry.close()
    del state, loader, variables

    # ---- correct: the reference follows the tapped steps ----
    t_ref = time.monotonic()
    prog = program_numbers(step, cell.config)
    step.opt1 = None                       # 5.6 GB of host memory at 697M
    batches, numbers = reference_batches(dataset, host_tap)
    ref = reference_first_steps(cell.config, spec, step.params0, batches)
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
            paths = TR.hlo_paths(built.train_step.lower(
                *step.arg_specs).compile().as_text())
            red = TR.reduce_trace(trace_dir, paths,
                                  cell.config.get("trace_groups", ()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red and red.get("steps"):
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
    result["window"] = {
        "seconds": window_s, "epochs": e, "rows": rows,
        "rows_per_s": rows / window_s, "setup_s": setup_s,
        "tokens_per_s": rows * cfg.seq_len / window_s,
        "steps_per_epoch": steps_per_epoch, "batch": batch,
        "reference_s": reference_s,
        # programs built inside the window (the program's own counter over
        # the whole window): must be 0
        "compiles": snap_end.get("compiles_total", 0.0)
        - snap0.get("compiles_total", 0.0)}
    result["numbers"] = {k: float(v) for k, v in numbers.items()}
    result["compared"] = compared
    return result
