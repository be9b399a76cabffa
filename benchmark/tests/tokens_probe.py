"""Does the sequence cell's step fit the chip, and what does a step take?

    python benchmark/tests/tokens_probe.py <workload> [seq_len ...]

For each sequence length (default: the configuration's): the program's own
state, loader and step as the driver builds them, a few epochs of the
normal loop, then the device's memory statistics and the seconds a step
took.  No reference and no result line: a probe for sizing a cell on the
chip, not run by the benchmark.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> None:
    import jax
    from benchmark.drivers import train_tokens as D
    from benchmark.lib import manifest as M
    from deepfake_detection_tpu.train import train_one_epoch
    cell = M.Cell(argv[0])
    if os.environ.get("BENCHMARK_ALLOW_CPU") != "1":
        D.require_chips(cell.chips)
    D.setup_cache(cell.cache_dir)
    for seq_len in [int(a) for a in argv[1:]] or \
            [int(cell.config["train"]["seq_len"])]:
        flags = list(cell.config["train_flags"])
        flags[flags.index("--seq-len") + 1] = str(seq_len)
        cell.config["train_flags"] = flags
        cell.config["train"]["seq_len"] = seq_len
        line = {"seq_len": seq_len}
        try:
            built = D.TokenBuilt(cell, os.path.join(cell.cache_dir, "probe"))
            dataset, variables, _ = D.make_inputs(cell, 1, built.global_batch)
            state = built.state_for(variables)
            loader, _ = built.loader_for(dataset, 1, 0)
            rng = built.rng_for(1)
            times = []
            for e in range(3):
                loader.set_epoch(e)
                t0 = time.monotonic()
                state, m = train_one_epoch(
                    e, built.train_step, state, loader, built.cfg,
                    jax.random.fold_in(rng, e),
                    lr_scheduler=built.lr_scheduler, world_size=built.n_dev)
                times.append(time.monotonic() - t0)
            line.update(epoch_s=times, steps=len(loader), loss=m["loss"],
                        step_s=times[-1] / len(loader))
            loader.close()
            del state, variables
        except Exception as e:          # noqa: BLE001 — the probe reports
            line["error"] = repr(e)[:2000]
        mem = jax.devices()[0].memory_stats() or {}
        line["memory"] = {k: mem.get(k) for k in (
            "bytes_limit", "peak_bytes_in_use", "peak_bytes_reserved",
            "largest_alloc_size")}
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
