"""The committed limits against the readings they were set from.

``benchmark/tests/readings/<config>.jsonl`` holds what the calibration runs
read on the chip at the cells' own sizes (``calibrate_train.py`` and the full
sets; PERF.md section 6 gives the ranges): the program's sound runs, the
float8 control and the half-batch fault, one line per seed and kind.  Under
the limits the configuration commits, ``judge`` must call every sound run
correct and every control and fault not correct.  The batch numbers
(``batch_gap``, ``target_gap``) came after these readings and are not in
them.
"""
import json
import os

import pytest

from benchmark.drivers import train as D
from benchmark.lib import manifest as M

HERE = os.path.dirname(os.path.abspath(__file__))
LATER = {"batch_gap", "target_gap"}


def _readings(config):
    path = os.path.join(HERE, "readings", config + ".jsonl")
    return [json.loads(line) for line in open(path) if line.strip()]


@pytest.mark.parametrize("config", ["flagship_v4_600", "effnet_b4_380"])
def test_committed_limits_separate_the_recorded_readings(config):
    limits = M.load_json(os.path.join(M.BENCH, "configs", config + ".json")
                         )["reference"]["limits"]
    held = set(limits) - LATER
    counts = {"program": 0, "control": 0, "half_batch": 0}
    for r in _readings(config):
        have = held & set(r["numbers"])
        if r["kind"] == "half_batch":
            if not have:
                continue
        elif have != held:
            continue                # an early reading without every number
        ok, compared = D.judge(r["numbers"], limits)
        assert ok is (r["kind"] == "program"), (r["kind"], r["seed"], compared)
        counts[r["kind"]] += 1
    assert counts["program"] >= 12 and counts["control"] >= 6 \
        and counts["half_batch"] >= 3, counts
