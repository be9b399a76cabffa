"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name ``BENCHMARK.json`` gives
(``benchmark/lib/manifest.py``).  The last line of standard output is the
result; the numbers that decided ``correct`` are also the last lines of
standard error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark.lib.manifest import Cell
    cell = Cell(args.workload)
    os.makedirs(cell.cache_dir, exist_ok=True)
    result = cell.driver().run(cell, args.seed, args.seconds,
                               bool(args.trace), T_START)
    compared = result.pop("compared")
    result["compared"] = compared          # comes last in the line
    for k, v in compared.items():
        print(f"compared {k} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
