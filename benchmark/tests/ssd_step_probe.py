"""The cell's own training step under either form of the state-space dual
scan, on the chip.

    python benchmark/tests/ssd_step_probe.py [pallas] [xla]

``ssd_probe.py`` times the operator alone and read the two forms as equal;
this builds ``train_granite4h_long``'s step as the driver does, with the
model's ``scan_impl`` set to each form in turn, and runs one epoch of ten
steps: the milliseconds a step by the host's clock around
``block_until_ready`` (the cell is device-bound), the loss and the peak
memory.  It is the reading that keeps the kernels on a TPU (PERF.md section
6: 902.5 ms against 991.5 ms).  One JSON line a form.  A probe for the chip,
not run by the benchmark.
"""

import gc
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CELL = "train_granite4h_long"


def main(argv) -> None:
    import jax
    from benchmark.drivers import train_seq as D
    from benchmark.lib import manifest as M
    cell = M.Cell(CELL)
    D.require_chips(cell.chips)
    D.setup_cache(cell.cache_dir)
    seed = 20261003
    for impl in argv or ["pallas", "xla"]:
        built = D.TokenBuilt(cell, os.path.join(cell.cache_dir,
                                                "probe_" + impl))
        # before ``state_for`` builds the step from the model
        built.model = built.model.clone(scan_impl=impl)
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
        print(json.dumps({
            "impl": impl, "first_step_s": times[0],
            "steps_ms": [round(1e3 * t, 2) for t in times[1:]],
            "median_ms": 1e3 * statistics.median(times[1:]),
            "loss": float(metrics["loss"]),
            "peak_bytes_in_use": mem.get("peak_bytes_in_use")}), flush=True)
        loader.close()
        del state, variables, built, loader
        gc.collect()


if __name__ == "__main__":
    main(sys.argv[1:])
