"""Two sets of six runs of one cell with the same seeds, then three traced
runs on further seeds; one line per run into chiprun_out/sets_<cell>.jsonl,
then the spreads (``spreads.py``).  The parent stays off JAX; every run is a
process of its own, as the driver's are.

    python3 benchmark/tests/full_sets.py <cell> <seconds> <first_seed> [runs]
"""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv):
    cell, seconds, s0 = argv[0], argv[1], int(argv[2])
    n = int(argv[3]) if len(argv) > 3 else 6
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = os.path.join(ROOT, "chiprun_out", f"sets_{cell}.jsonl")
    plan = [(s, i, 0) for s in (1, 2) for i in range(n)] + \
        [(3, i, 1) for i in (6, 7, 8)]
    with open(out, "w") as f:
        for set_no, i, trace in plan:
            seed = s0 + i * 1000003
            t0 = time.time()
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                 "--workload", cell, "--seed", str(seed), "--seconds",
                 seconds, "--trace", str(trace)], cwd=ROOT,
                capture_output=True, text=True)
            last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() \
                else ""
            try:
                result = json.loads(last)
            except ValueError:
                result = None
            f.write(json.dumps({"set": set_no, "seed": seed, "trace": trace,
                                "rc": p.returncode,
                                "wall_s": time.time() - t0,
                                "result": result}) + "\n")
            f.flush()
            if p.returncode != 0 or result is None:
                print(p.stderr[-1500:], flush=True)
    sys.path.insert(0, HERE)
    import spreads
    spreads.main(out)


if __name__ == "__main__":
    main(sys.argv[1:])
