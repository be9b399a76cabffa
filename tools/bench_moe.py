"""The routed expert layer (``ops/moe.py``): what its step needs with no
chip, and what each form of its grouped products costs on one.

Two modes, one JSON line a reading::

    python tools/bench_moe.py --aot lfm2_24b_a2b_5l [-b 4 --grad-accum 2]
    python tools/bench_moe.py --step train_lfm2moe_8k pallas xla \\
        pallas:512,512,512 pallas::8

* ``--aot <configuration>`` (no chip): the runner's whole train step for the
  flags in ``benchmark/configs/<configuration>.json`` (flags after it
  override them: argparse keeps the last), every kernel compiled, for a
  described v5e.  Prints ``memory_analysis()`` and the Mosaic calls under
  the scope ``moe_experts``; ``--text FILE`` keeps the compiled text.
* ``--step <cell>`` (chip): the cell's own training step as the benchmark's
  driver builds it, ten steps a form: milliseconds a step, the loss, the
  step's routing counts, the peak memory.  A form is
  ``impl[:tm,tk,tn[:headroom]]``: ``pallas`` (the megablox kernels) or
  ``xla`` (``jax.lax.ragged_dot``), the kernels' tiling, and the first
  capacity over the uniform share of the sorted rows (the op's is 2; 8,
  where an eighth of the experts is held: always every row).  An op timed
  alone mis-predicts the step (PERF.md section 6, PRs 27, 29, 30): ask the
  cell.

A probe, not run by the benchmark and not a test.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _emit(**row) -> None:
    print(json.dumps(row), flush=True)


def aot(config: str, extra, text_file=None) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from deepfake_detection_tpu.config import TrainConfig
    from deepfake_detection_tpu.models import init_model
    from deepfake_detection_tpu.ops import causal_conv, moe
    from deepfake_detection_tpu.parallel import (batch_sharding,
                                                 make_train_mesh,
                                                 replicated_sharding,
                                                 train_state_shardings)
    from deepfake_detection_tpu.runners import train as T
    from deepfake_detection_tpu.train import create_train_state

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        flags = json.load(f)["train_flags"] + list(extra)
    # this process's backend is the CPU: have every kernel compiled as on
    # the chip, and each op take the form it takes there
    for name in ("flash_attention", "causal_conv", "moe"):
        importlib.import_module("deepfake_detection_tpu.ops." + name) \
            .resolve_interpret = lambda interpret, kernel: False
    causal_conv.causal_conv_impl = functools.partial(
        causal_conv.causal_conv_impl, backend="tpu")
    moe.moe_impl = functools.partial(moe.moe_impl, backend="tpu")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_train_mesh(batch=1, model=1, devices=topo.devices[:1])
    cfg = TrainConfig.from_args(list(flags))
    program = T.build_program(cfg, mesh=mesh)
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
    _emit(config=config, batch=cfg.batch_size, grad_accum=cfg.grad_accum,
          parameters=sum(x.size for x in jax.tree.leaves(state.params)),
          moe_layers=list(program.moe_layers),
          trace_lower_s=round(t1 - t0, 1),
          compile_s=round(time.monotonic() - t1, 1),
          argument_bytes=mem.argument_size_in_bytes,
          temp_bytes=mem.temp_size_in_bytes,
          total_bytes=mem.argument_size_in_bytes + mem.temp_size_in_bytes
          + mem.output_size_in_bytes - mem.alias_size_in_bytes,
          mosaic_calls_under_moe_experts=sum(
              1 for line in text.splitlines()
              if "tpu_custom_call" in line and "moe_experts" in line))


def _ten_steps(cell, impl: str, seed: int):
    import jax

    from benchmark.drivers import train_seq as D
    built = D.TokenBuilt(cell, os.path.join(cell.cache_dir, "moe_probe"))
    built.model = built.model.clone(moe_impl=impl)
    dataset, variables, _ = D.make_inputs(cell, seed, built.global_batch)
    state = built.state_for(variables)
    del variables
    loader, _ = built.loader_for(dataset, seed, 0)
    loader.set_epoch(0)
    rng = built.rng_for(seed)
    times = []
    try:
        for x, y in loader:
            t0 = time.perf_counter()
            state, metrics = built.train_step(state, x, y, rng)
            jax.block_until_ready(metrics["loss"])
            times.append(time.perf_counter() - t0)
    finally:
        loader.close()
    mem = jax.devices()[0].memory_stats() or {}
    return dict(first_step_s=times[0],
                steps_ms=[round(1e3 * t, 2) for t in times[1:]],
                median_ms=1e3 * statistics.median(times[1:]),
                loss=float(metrics["loss"]),
                moe_counts=[int(c) for c in metrics["moe_counts"]],
                peak_bytes_in_use=mem.get("peak_bytes_in_use"))


def cell_step(cell_name: str, forms) -> None:
    from benchmark.drivers import train_seq as D
    from benchmark.lib import manifest as M
    from deepfake_detection_tpu.ops import moe
    cell = M.Cell(cell_name)
    D.require_chips(cell.chips)
    D.setup_cache(cell.cache_dir)
    seed = 20261004
    tiling, headroom = moe._TILING, moe._HEADROOM
    for form in forms:
        impl, _, rest = form.partition(":")
        tiles, _, room = rest.partition(":")
        moe._TILING = tuple(int(t) for t in tiles.split(",")) if tiles \
            else tiling
        moe._HEADROOM = float(room) if room else headroom
        try:
            _emit(cell=cell_name, form=form, **_ten_steps(cell, impl, seed))
        except Exception as e:      # noqa: BLE001 — the next form still runs
            _emit(cell=cell_name, form=form, error=repr(e)[:400])
        gc.collect()
    moe._TILING, moe._HEADROOM = tiling, headroom


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--aot", metavar="CONFIGURATION")
    mode.add_argument("--step", metavar="CELL")
    ap.add_argument("--text", metavar="FILE")
    args, rest = ap.parse_known_args(argv)
    if args.aot:
        aot(args.aot, rest, args.text)
    else:
        cell_step(args.step, rest or ["pallas", "xla"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
