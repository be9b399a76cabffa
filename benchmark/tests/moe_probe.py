"""How often the program and the plain reference select different experts.

    python benchmark/tests/moe_probe.py <workload> <seed> [rows]

A selection is a step and not a rounding: where the bfloat16 program's
router input differs from the float32 reference's by a rounding, a score
that sits within it of the fourth and fifth largest changes the token's set
of experts, and that token's expert output changes by a whole expert.  This
reads, for the cell's seeded weights and the first ``rows`` documents of its
pool (default 2), the share of (token, selected expert) pairs of the
program's forward that the reference did not select, a layer and in all, and
the same among the pairs that fall on held experts (those move this chip's
numbers).  One JSON line.  Run on the chip at the cell's own size (the CPU
with ``BENCHMARK_ALLOW_CPU=1`` and ``BENCHMARK_MANIFEST`` for a tiny cell);
not run by the benchmark's own runs.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import reference
    from benchmark.drivers import train_seq as D
    from benchmark.lib import manifest as M
    workload, seed = argv[0], int(argv[1])
    rows = int(argv[2]) if len(argv) > 2 else 2
    man = M.load_json(os.environ["BENCHMARK_MANIFEST"]) \
        if os.environ.get("BENCHMARK_MANIFEST") else None
    cell = M.Cell(workload, man)
    if os.environ.get("BENCHMARK_ALLOW_CPU") != "1":
        D.require_chips(cell.chips)
    D.setup_cache(cell.cache_dir)
    built = D.TokenBuilt(cell, os.path.join(cell.cache_dir, "moe_probe"))
    dataset, variables, spec = D.make_inputs(cell, seed, built.global_batch)
    ids = jnp.asarray(dataset.pool[:rows])
    model = built.model
    first, count = model.held
    _, sown = jax.jit(lambda v, x: model.apply(
        v, x, mutable=["moe_selected"], method="hidden"))(variables, ids)
    R = reference.model(cell.config)
    line = {"workload": workload, "seed": seed, "rows": rows,
            "tokens": int(ids.size), "layers": {}}
    differ = pairs = differ_held = pairs_held = 0
    for r in range(rows):
        ref = R.selections(variables["params"], variables["batch_stats"],
                           ids[r], spec)
        for name, want in ref.items():
            got = np.asarray(sown["moe_selected"][name]["sel"][0]).reshape(
                rows, -1, want.shape[1])[r]
            want = np.asarray(want)
            missed = ~(got[:, :, None] == want[:, None, :]).any(-1)
            held = (got >= first) & (got < first + count)
            layer = line["layers"].setdefault(name, [0, 0])
            layer[0] += int(missed.sum())
            layer[1] += missed.size
            differ += int(missed.sum())
            pairs += missed.size
            differ_held += int((missed & held).sum())
            pairs_held += int(held.sum())
    line["layers"] = {k: v[0] / v[1] for k, v in line["layers"].items()}
    line.update(selections_that_differ=differ / pairs,
                held_selections_that_differ=differ_held / max(pairs_held, 1),
                pairs=pairs, pairs_held=pairs_held)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
