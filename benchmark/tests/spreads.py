"""Spreads of the end-to-end metrics over two sets of runs, as the bounds
are set from them: per set the distance between the first and third
quartile (``statistics.quantiles(values, n=4)``) as a share of the median;
the wider of the two sets; and the second set's median against the first's.

    python benchmark/tests/spreads.py chiprun_out/sets_<cell>.jsonl
"""
import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(path):
    rows = [json.loads(line) for line in open(path) if line.strip()]
    bad = [r for r in rows if r["rc"] != 0 or not r["result"]
           or not r["result"]["correct"]]
    print(f"{len(rows)} runs, {len(bad)} not correct or failed:",
          [(r["seed"], r["rc"]) for r in bad])
    sets = {1: {}, 2: {}}
    for r in rows:
        if r["set"] in sets and r["result"]:
            for k, v in r["result"]["metrics"].items():
                sets[r["set"]].setdefault(k, []).append(v["value"])
    for k in sets[1]:
        a, b = sets[1][k], sets[2].get(k, [])
        line = f"{k}: set1 {['%.4g' % v for v in a]}"
        if len(a) >= 2:
            line += f" spread {spread(a):.4f}"
        if len(b) >= 2:
            line += (f" | set2 {['%.4g' % v for v in b]} spread "
                     f"{spread(b):.4f} | median2/median1 "
                     f"{statistics.median(b) / statistics.median(a):.4f}")
        print(line)
    for r in rows:
        res = r["result"]
        if res:
            print(r["set"], r["seed"], r["trace"], "wall %.0fs" % r["wall_s"],
                  "correct", res["correct"],
                  {k: round(v["value"], 4) for k, v in res["metrics"].items()},
                  {k: round(v["value"], 4)
                   for k, v in res["compared"].items()},
                  "mem %.2f GB" % (res["device"]["memory_peak_bytes"] / 1e9),
                  "busy/window", res["device"].get("busy_s"),
                  res["device"].get("window_s"))


if __name__ == "__main__":
    main(sys.argv[1])
