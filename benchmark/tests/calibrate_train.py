"""Readings for a training cell's limits, many seeds in one process.

    python benchmark/tests/calibrate_train.py <workload> <seed,seed,...> \
        [program] [control] [half_batch]

For each seed: the program's first three steps through the window's own call
and feed (``program``), the control (the reference put in the program's
place, computed in float8; ``control``) and the planted fault
(``half_batch``), each compared with the plain reference.  One JSON line per
seed on standard output.  Run on the chip at the cell's own size; not run by
the benchmark's own runs.
"""

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def worst_leaves(prog, ref, params0, k=3):
    """Names and sizes of the leaves that read the widest gaps."""
    import jax
    import numpy as np
    from benchmark.drivers import train as D
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params0)[0]]
    mean_abs = [float(np.mean(np.abs(a))) for a in jax.tree.leaves(params0)]
    out = {}
    for key in ("grad1", "delta"):
        gaps = D.leaf_gaps(prog[key], ref[key])
        med = float(np.median(ref[key]))
        top = sorted(range(len(gaps)), key=lambda i: -gaps[i])[:k]
        out[key] = [{"leaf": names[i], "gap": gaps[i],
                     "ref_over_median": ref[key][i] / med,
                     "prog_norm": prog[key][i], "ref_norm": ref[key][i],
                     "mean_abs_param": mean_abs[i]} for i in top]
    return out


def main(argv) -> None:
    import jax
    from benchmark.drivers import train as D
    from benchmark.lib import manifest as M
    from deepfake_detection_tpu.train import train_one_epoch
    workload, seeds = argv[0], [int(s) for s in argv[1].split(",")]
    what = argv[2:] or ["program", "control", "half_batch"]
    man = M.load_json(os.environ["BENCHMARK_MANIFEST"]) \
        if os.environ.get("BENCHMARK_MANIFEST") else None
    cell = M.Cell(workload, man)
    if os.environ.get("BENCHMARK_ALLOW_CPU") != "1":
        D.require_chips(cell.chips)
    D.setup_cache(cell.cache_dir)
    out_dir = os.path.join(cell.cache_dir, "calib")
    shutil.rmtree(out_dir, ignore_errors=True)
    built = D.Built(cell, out_dir)
    batch = built.global_batch
    control = cell.config["reference"].get("control", "fp8")
    for seed in seeds:
        t0 = time.time()
        line = {"seed": seed}
        dataset, variables, spec = D.make_inputs(cell, seed, batch)
        dataset.length = D.CHECK_STEPS * batch
        host_vars = jax.device_get(variables)
        ref = None
        for mode in [m for m in what if m in ("program", "half_batch")]:
            state = built.state_for(jax.tree.map(jax.numpy.asarray,
                                                 host_vars))
            lseed = seed % (2 ** 31 - 1)
            loader, tap, mix_tap = built.loader_for(dataset, lseed,
                                                    D.CHECK_STEPS)
            step = D.StepTap(built.train_step,
                             fault=None if mode == "program" else mode)
            loader.set_epoch(0)
            state, _ = train_one_epoch(
                0, step, state, loader, built.cfg,
                jax.random.fold_in(built.rng_for(seed), 0),
                lr_scheduler=built.lr_scheduler, world_size=built.n_dev)
            loader.close()
            del state
            prog = D.program_numbers(step, cell.config)
            batches, numbers = D.reference_batches(cell.config, spec,
                                                   dataset, tap, mix_tap)
            if ref is None:
                ref = D.reference_first_steps(
                    cell.config, spec, step.params0, step.stats0,
                    batches, lseed)
                params0, stats0 = step.params0, step.stats0
                line["ref_losses"] = ref["losses"]
            line[mode] = dict(numbers, **D.compare(prog, ref))
            line[mode]["losses"] = prog["losses"]
            if mode == "program":
                line["worst_leaves"] = worst_leaves(prog, ref, step.params0)
        if "control" in what and ref is not None:
            ctl = D.reference_first_steps(cell.config, spec, params0, stats0,
                                          batches, lseed, quant=control)
            line["control"] = D.compare(ctl, ref)
            line["control"]["losses"] = ctl["losses"]
            line["control"]["judged_correct"] = D.judge(
                line["control"], cell.config["reference"]["limits"])[0]
        line["seconds"] = time.time() - t0
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
