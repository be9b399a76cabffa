"""Readings for a sequence cell's limits, many seeds in one process.

    python benchmark/tests/calibrate_tokens.py <workload> <seed,seed,...> \
        [program] [control] [half_batch] [state_unchanged] \
        [window_ignored] [memory_wrong_layer]

For each seed: the program's first three steps through the window's own
call and feed (``program``), the control (the reference put in the program's
place, computed in float8; ``control``) and the planted faults of
``drivers/train_tokens.py``, each compared with the plain reference.  One
JSON line per seed on standard output, each mode with its three leaves of
the widest ``delta_gap`` by name (``worst_leaves``: the worst-leaf norms
that no limit holds, PERF.md section 6).  Run on the chip at the cell's own
size; not run by the benchmark's own runs.
"""

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def worst_leaves(prog, ref, params0, k=3):
    """The ``k`` leaves (of those ``compare`` counts as moved) whose norm of
    change is furthest from the reference's, with what they are."""
    import jax
    import numpy as np
    from benchmark.drivers.train import leaf_gaps
    flat = jax.tree_util.tree_flatten_with_path(params0)[0]
    gaps = leaf_gaps(prog["delta"], ref["delta"])
    g1gaps = leaf_gaps(prog["grad1"], ref["grad1"])
    floor = 1e-3 * float(np.median(ref["grad1"]))
    order = [i for i in np.argsort(gaps)[::-1] if ref["grad1"][i] >= floor]
    return [{"leaf": jax.tree_util.keystr(flat[i][0]),
             "size": int(np.size(flat[i][1])), "delta_gap": gaps[i],
             "grad1_gap": g1gaps[i], "prog_delta": prog["delta"][i],
             "ref_delta": ref["delta"][i], "ref_grad1": ref["grad1"][i]}
            for i in order[:k]]


def main(argv) -> None:
    import jax
    from benchmark.drivers import train_tokens as D
    from benchmark.lib import manifest as M
    from deepfake_detection_tpu.train import train_one_epoch
    workload, seeds = argv[0], [int(s) for s in argv[1].split(",")]
    what = argv[2:] or ["program", "control"] + list(D.STEP_FAULTS
                                                     + D.MODEL_FAULTS)
    man = M.load_json(os.environ["BENCHMARK_MANIFEST"]) \
        if os.environ.get("BENCHMARK_MANIFEST") else None
    cell = M.Cell(workload, man)
    if os.environ.get("BENCHMARK_ALLOW_CPU") != "1":
        D.require_chips(cell.chips)
    D.setup_cache(cell.cache_dir)
    out_dir = os.path.join(cell.cache_dir, "calib")
    shutil.rmtree(out_dir, ignore_errors=True)
    limits = cell.config["reference"]["limits"]
    control = cell.config["reference"].get("control", "fp8")
    builts = {}
    for seed in seeds:
        t0 = time.time()
        line = {"seed": seed}
        ref = params0 = batches = None
        for mode in [m for m in what if m != "control"]:
            t1 = time.time()
            fault = None if mode == "program" else mode
            key = fault if fault in D.MODEL_FAULTS else None
            if key not in builts:
                builts[key] = D.TokenBuilt(cell, out_dir, key)
            built = builts[key]
            dataset, variables, spec = D.make_inputs(cell, seed,
                                                     built.global_batch)
            dataset.length = D.CHECK_STEPS * built.global_batch
            state = built.state_for(variables)
            lseed = seed % (2 ** 31 - 1)
            loader, tap = built.loader_for(dataset, lseed, D.CHECK_STEPS)
            step = D.TokenStepTap(
                built.train_step,
                fault=fault if fault in D.STEP_FAULTS else None)
            loader.set_epoch(0)
            state, _ = train_one_epoch(
                0, step, state, loader, built.cfg,
                jax.random.fold_in(built.rng_for(seed), 0),
                lr_scheduler=built.lr_scheduler, world_size=built.n_dev)
            loader.close()
            del state, variables
            prog = D.program_numbers(step, cell.config)
            step.opt1 = None
            if ref is None:
                batches, numbers = D.reference_batches(dataset, tap)
                params0 = step.params0
                t2 = time.time()
                ref = D.reference_first_steps(cell.config, spec, params0,
                                              batches)
                line["ref_losses"] = ref["losses"]
                line["reference_s"] = time.time() - t2
            else:
                _, numbers = D.reference_batches(dataset, tap)
            line[mode] = dict(numbers, **D.compare(prog, ref))
            line[mode]["losses"] = prog["losses"]
            line[mode]["worst_leaves"] = worst_leaves(prog, ref, params0)
            line[mode]["judged_correct"] = D.judge(line[mode], limits)[0]
            line[mode]["seconds"] = time.time() - t1
            print(json.dumps({"seed": seed, "mode": mode, **line[mode]}),
                  file=sys.stderr, flush=True)     # kept if a later mode dies
            del prog, step
        if "control" in what and ref is not None:
            t1 = time.time()
            ctl = D.reference_first_steps(cell.config, spec, params0,
                                          batches, quant=control)
            line["control"] = D.compare(ctl, ref)
            line["control"]["losses"] = ctl["losses"]
            line["control"]["worst_leaves"] = worst_leaves(ctl, ref, params0)
            line["control"]["judged_correct"] = D.judge(
                line["control"], limits)[0]
            line["control"]["seconds"] = time.time() - t1
        line["seconds"] = time.time() - t0
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
