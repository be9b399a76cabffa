"""Readings for the limits of a cell of ``drivers/train_seq.py``, many seeds
in one process.

    python benchmark/tests/calibrate_seq.py <workload> <seed,seed,...> \
        [program] [control] [half_batch] [state_unchanged] [<model fault> ...]

``calibrate_tokens.py`` for the family-blind driver: for each seed the
program's first three steps through the window's own call and feed
(``program``), the control (the reference put in the program's place,
computed one precision lower; ``control``), the step faults and the model
faults of the file the configuration names (``reference.faults``), each
compared with the plain reference.  One JSON line per seed on standard
output.  Run on the chip at the cell's own size; not run by the benchmark's
own runs.
"""

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> None:
    import jax
    from benchmark.drivers import train_seq as D
    from benchmark.lib import manifest as M
    from benchmark.tests.calibrate_tokens import worst_leaves
    from deepfake_detection_tpu.train import train_one_epoch
    workload, seeds = argv[0], [int(s) for s in argv[1].split(",")]
    man = M.load_json(os.environ["BENCHMARK_MANIFEST"]) \
        if os.environ.get("BENCHMARK_MANIFEST") else None
    cell = M.Cell(workload, man)
    faults = D.model_faults(cell.config)
    what = argv[2:] or ["program", "control"] + list(D.STEP_FAULTS
                                                     + faults.MODEL_FAULTS)
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
            key = fault if fault in faults.MODEL_FAULTS else None
            if key not in builts:
                builts[key] = D.TokenBuilt(cell, out_dir)
                builts[key].model = faults.faulty_model(builts[key].model,
                                                        key)
            built = builts[key]
            dataset, variables, spec = D.make_inputs(cell, seed,
                                                     built.global_batch)
            dataset.length = D.CHECK_STEPS * built.global_batch
            state = built.state_for(variables)
            loader, tap = built.loader_for(dataset, seed % (2 ** 31 - 1),
                                           D.CHECK_STEPS)
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
