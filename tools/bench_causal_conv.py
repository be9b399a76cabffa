"""The Mamba layers' causal convolution (``ops/causal_conv.py``): what the
compiler makes of it with no chip, and what it costs on one.

Three modes, one JSON line a reading::

    python tools/bench_causal_conv.py --aot granite4_h_micro_10l [--old]
    python tools/bench_causal_conv.py --op [--rows 64,128,256]
    python tools/bench_causal_conv.py --step train_granite4h_long [op] [old]
    python tools/bench_causal_conv.py --census granite4_h_micro_10l

* ``--aot <configuration>`` (no chip): the runner's whole train step for the
  flags in ``benchmark/configs/<configuration>.json``, every kernel compiled,
  for a described v5e.  Prints ``memory_analysis()`` and every compiled
  instruction under the convolution's scope (``ssd_conv`` / ``mamba_conv``)
  or with a ``bf16[channels]`` output, and counts the two fusions PR 31 took
  out: one with channel-sum outputs rounded to bfloat16, one with several
  whole-tensor outputs.  ``--old`` compiles the same step with the
  expression the op replaced, for the comparison.  ``--text FILE`` keeps the
  compiled text.
* ``--op`` (chip): the op alone at both cells' shapes, ``(1, 16384, 4352)``
  and ``(1, 16384, 5120)`` bfloat16: a forward launch and a backward launch
  (all three gradients), each from a chain of 16 in one program (one launch
  by the host's clock is the dispatch), the kernels at each row tile of
  ``--rows``, the array form and the expression the op replaced.  An op
  timed alone mis-predicts the step (PERF.md section 6, PRs 27, 29, 30):
  this chooses a tile, the cell decides.
* ``--step <cell>`` (chip): the cell's own training step as the benchmark's
  driver builds it, ten steps each with the op (``op``; ``op:1024`` with
  another row tile) and with the expression it replaced (``old``):
  milliseconds a step, the loss, the peak memory, and the model's census
  ``causal_conv_layers``.
* ``--census <configuration>`` (chip, or the CPU for what the CPU chooses):
  ``runners/train.py:main`` with the configuration's flags up to its first
  epoch, which is not run; prints the ``run_start`` event of the run's
  ``telemetry.jsonl``, where ``causal_conv_kernel_layers`` /
  ``causal_conv_xla_layers`` say which form the step's Mamba layers take.

A probe, not run by the benchmark and not a test.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import re
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SHAPES = ((1, 16384, 4352), (1, 16384, 5120))
_MODELS = ("deepfake_detection_tpu.models.granite4h",
           "deepfake_detection_tpu.models.phi4flash")


def replaced_expression(x, w, b):
    """The convolution as both models wrote it before PR 31."""
    import flax.linen as nn
    import jax.numpy as jnp
    k, l = w.shape[0], x.shape[1]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return nn.silu(sum(pad[:, i:i + l] * w[i].astype(x.dtype)
                       for i in range(k)) + b.astype(x.dtype))


def _bind(fn) -> None:
    """What the two models call for their convolution, from the next trace
    on."""
    import importlib
    for name in _MODELS:
        importlib.import_module(name).causal_conv1d = fn


def _emit(**row) -> None:
    print(json.dumps(row), flush=True)


# ---------------------------------------------------------------------------
# --aot: the whole step for a described chip
# ---------------------------------------------------------------------------

def aot(config: str, old: bool, text_file) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import dataclasses
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from deepfake_detection_tpu.config import TrainConfig
    from deepfake_detection_tpu.models import init_model
    from deepfake_detection_tpu.ops import causal_conv
    from deepfake_detection_tpu.parallel import (batch_sharding,
                                                 make_train_mesh,
                                                 replicated_sharding,
                                                 train_state_shardings)
    from deepfake_detection_tpu.runners import train as T
    from deepfake_detection_tpu.train import create_train_state

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        flags = json.load(f)["train_flags"]
    # this process's backend is the CPU: have every kernel compiled as on
    # the chip, and the op take the form it takes there
    for name in ("flash_attention", "selective_scan", "ssd", "causal_conv"):
        importlib.import_module("deepfake_detection_tpu.ops." + name) \
            .resolve_interpret = lambda interpret, kernel: False
    causal_conv.causal_conv_impl = functools.partial(
        causal_conv.causal_conv_impl, backend="tpu")
    if old:
        _bind(replaced_expression)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_train_mesh(batch=1, model=1, devices=topo.devices[:1])
    cfg = TrainConfig.from_args(list(flags))
    program = T.build_program(cfg, mesh=mesh)
    program = dataclasses.replace(
        program, model=program.model.clone(scan_impl="pallas"))
    state = jax.eval_shape(lambda: create_train_state(init_model(
        program.model, jax.random.PRNGKey(0), (1, 8), training=True,
        dtype=jnp.int32), program.tx))
    shardings = train_state_shardings(state, mesh, fsdp=False,
                                      axis=program.batch_axis)
    step = T.build_steps(program, shardings)[0]
    ids = jax.ShapeDtypeStruct((program.global_batch, cfg.seq_len),
                               jnp.int32, sharding=batch_sharding(mesh))
    key = jax.random.PRNGKey(0)
    t0 = time.monotonic()
    lowered = step.lower(
        jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), state, shardings), ids, ids,
        jax.ShapeDtypeStruct(key.shape, key.dtype,
                             sharding=replicated_sharding(mesh)))
    t1 = time.monotonic()
    compiled = lowered.compile()
    text = compiled.as_text()
    if text_file:
        with open(text_file, "w") as f:
            f.write(text)
    mem = compiled.memory_analysis()
    # the convolution's width is its kernel's: (d_conv, channels)
    channels = {leaf.shape[1] for path, leaf in
                jax.tree_util.tree_flatten_with_path(state.params)[0]
                if "conv_kernel" in jax.tree_util.keystr(path)}.pop()
    found = census(text, channels, cfg.seq_len)
    for line in found.pop("lines"):
        _emit(instruction=line)
    _emit(config=config, form="old" if old else "op",
          trace_lower_s=round(t1 - t0, 1),
          compile_s=round(time.monotonic() - t1, 1),
          argument_bytes=mem.argument_size_in_bytes,
          temp_bytes=mem.temp_size_in_bytes,
          total_bytes=mem.argument_size_in_bytes + mem.temp_size_in_bytes
          + mem.output_size_in_bytes - mem.alias_size_in_bytes, **found)


def census(text: str, channels: int, seq_len: int):
    """The compiled step's fusions and kernels of the convolution: every
    instruction whose ``op_name`` holds its scope, or with several
    ``bf16[channels]`` sums among its outputs (one alone is some layer's
    bias gradient).  Counts the fusions with such sums, those with more than
    one whole ``bf16[1, L, channels]`` output, and the Mosaic calls under
    the scope."""
    small = f"bf16[{channels}]"
    whole = f"bf16[1,{seq_len},{channels}]"
    lines, sums, multi, kernels = [], 0, 0, 0
    for line in text.splitlines():
        parts = line.strip().split(" = ", 1)
        if len(parts) < 2 or not re.search(r"\b(fusion|custom-call)\(",
                                           parts[1]):
            continue
        outputs = re.split(r"\b(?:fusion|custom-call)\(", parts[1])[0]
        name = re.search(r'op_name="([^"]*)"', parts[1])
        name = name.group(1) if name else ""
        scoped = re.search(r"ssd_conv|mamba_conv", name)
        n_small, n_whole = outputs.count(small), outputs.count(whole)
        if not (scoped or n_small > 1):
            continue
        sums += n_small > 1
        multi += n_whole > 1
        kernels += bool(scoped) and "tpu_custom_call" in parts[1]
        lines.append(f"{parts[0].split()[-1]} {n_small}x{small} "
                     f"{n_whole}x{whole} {name[-70:]}")
    return dict(lines=lines, channel_sum_fusions=sums,
                multi_output_fusions=multi, kernels_under_scope=kernels)


# ---------------------------------------------------------------------------
# --op: the op alone, on the chip
# ---------------------------------------------------------------------------

CHAIN = 16


def _chain_ms(step, carry, *rest):
    """Milliseconds a launch, from ``CHAIN`` launches in one program, each
    fed by the one before: a launch alone, timed by the host's clock, is
    the dispatch (1.0-1.2 ms for any of these, PR 31)."""
    import jax

    @jax.jit
    def run(carry, *rest):
        for _ in range(CHAIN):
            carry = step(carry, *rest)
        return carry
    jax.block_until_ready(run(carry, *rest))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(run(carry, *rest))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times) / CHAIN


def op_alone(rows) -> None:
    import jax
    import jax.numpy as jnp

    from deepfake_detection_tpu.ops import causal_conv
    assert jax.default_backend() == "tpu", jax.default_backend()
    forms = [("kernels", r) for r in rows] + [("array", None),
                                              ("replaced", None)]
    for shape in SHAPES:
        k = jax.random.split(jax.random.PRNGKey(0), 4)
        x, dy = (jax.random.normal(i, shape, jnp.bfloat16) for i in k[:2])
        w = jax.random.normal(k[2], (4, shape[-1])) * 0.5
        b = jax.random.normal(k[3], shape[-1:]) * 0.1
        for form, r in forms:
            if r:
                causal_conv._ROWS = r
            fn = {"kernels": lambda *a: causal_conv.causal_conv1d(
                      *a, impl="pallas"),
                  "array": lambda *a: causal_conv.causal_conv1d(
                      *a, impl="xla"),
                  "replaced": replaced_expression}[form]

            def backward(carry, x, w, b, fn=fn):
                # dx is the next launch's dy; dw and db are kept alive
                dy, dw, db = carry
                gx, gw, gb = jax.vjp(fn, x, w, b)[1](dy)
                return gx, dw + gw, db + gb
            _emit(shape=list(shape), form=form, rows=r,
                  forward_ms=_chain_ms(fn, x, w, b),
                  backward_ms=_chain_ms(
                      backward, (dy, jnp.zeros_like(w), jnp.zeros_like(b)),
                      x, w, b))


# ---------------------------------------------------------------------------
# --step: the cell's own step, on the chip
# ---------------------------------------------------------------------------

def cell_step(cell_name: str, forms) -> None:
    import jax

    from benchmark.drivers import train_seq as D
    from benchmark.lib import manifest as M
    from deepfake_detection_tpu.ops.causal_conv import causal_conv1d
    cell = M.Cell(cell_name)
    D.require_chips(cell.chips)
    D.setup_cache(cell.cache_dir)
    seed = 20261004
    from deepfake_detection_tpu.ops import causal_conv
    for form in forms:
        kind, _, rows = form.partition(":")
        _bind(causal_conv1d if kind == "op" else replaced_expression)
        if rows:
            causal_conv._ROWS = int(rows)
        built = D.TokenBuilt(cell, os.path.join(cell.cache_dir,
                                                "conv_probe_" + form))
        dataset, variables, _ = D.make_inputs(cell, seed, built.global_batch)
        state = built.state_for(variables)
        loader, _ = built.loader_for(dataset, seed, 0)
        loader.set_epoch(0)
        rng = built.rng_for(seed)
        times = []
        for x, y in loader:
            t0 = time.perf_counter()
            state, metrics = built.train_step(state, x, y, rng)
            jax.block_until_ready(metrics["loss"])
            times.append(time.perf_counter() - t0)
        mem = jax.devices()[0].memory_stats() or {}
        _emit(cell=cell_name, form=form, first_step_s=times[0],
              steps_ms=[round(1e3 * t, 2) for t in times[1:]],
              median_ms=1e3 * statistics.median(times[1:]),
              loss=float(metrics["loss"]),
              peak_bytes_in_use=mem.get("peak_bytes_in_use"),
              causal_conv_layers=list(
                  built.model.causal_conv_layers(built.cfg.seq_len)))
        loader.close()
        del state, variables, built, loader
        gc.collect()
    _bind(causal_conv1d)


# ---------------------------------------------------------------------------
# --census: what the runner's event log says
# ---------------------------------------------------------------------------

def run_start_census(config: str, out_dir: str) -> None:
    import glob

    from deepfake_detection_tpu.config import TrainConfig
    from deepfake_detection_tpu.runners import train as T
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        flags = json.load(f)["train_flags"]

    def no_epoch(*args, **kwargs):
        raise KeyboardInterrupt        # main's own way out: run_end follows

    T.train_one_epoch = no_epoch
    T.main(TrainConfig.from_args(list(flags) + ["--output", out_dir]))
    for path in glob.glob(os.path.join(out_dir, "**", "telemetry.jsonl"),
                          recursive=True):
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                if row.get("event") == "run_start":
                    _emit(config=config, **row)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--aot", metavar="CONFIGURATION")
    mode.add_argument("--op", action="store_true")
    mode.add_argument("--step", metavar="CELL")
    mode.add_argument("--census", metavar="CONFIGURATION")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "causal_conv_census"))
    ap.add_argument("--old", action="store_true")
    ap.add_argument("--text", metavar="FILE")
    ap.add_argument("--rows", default="64,128,256,512")
    ap.add_argument("forms", nargs="*", default=["op", "old"])
    args = ap.parse_args(argv)
    if args.aot:
        aot(args.aot, args.old, args.text)
    elif args.op:
        op_alone([int(r) for r in args.rows.split(",")])
    elif args.census:
        run_start_census(args.census, args.out)
    else:
        cell_step(args.step, args.forms)
    return 0


if __name__ == "__main__":
    sys.exit(main())
