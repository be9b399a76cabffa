"""The five metrics that read the loop thread's step record and the split of
the loader's wait (PR 34), each from a pair of hand-made snapshots: the
value, nothing (never 0) where the program has no such counter, as the
commit before the record has not, and nothing where the denominator did not
rise."""
import pytest

from benchmark.lib import manifest as M
from benchmark.metrics import counters_share

C0 = {"steps_total": 21.0, "step_seconds_total": 10.0,
      "device_wait_seconds_total": 1.0, "data_wait_seconds_total": 8.0,
      "normal_steps_total": 10.0, "normal_step_seconds_total": 1.02,
      "slow_step_excess_seconds_total": 0.5,
      "step_dispatch_seconds_total": 0.4,
      "step_h2d_block_seconds_total": 0.1,
      "step_prologue_block_seconds_total": 6.0}
C1 = {"steps_total": 48.0, "step_seconds_total": 12.7,
      "device_wait_seconds_total": 1.2, "data_wait_seconds_total": 9.9,
      "normal_steps_total": 30.0, "normal_step_seconds_total": 3.06,
      "slow_step_excess_seconds_total": 0.581,
      "step_dispatch_seconds_total": 0.94,
      "step_h2d_block_seconds_total": 0.154,
      "step_prologue_block_seconds_total": 7.69}
OLD = {"steps_total": 48.0, "step_seconds_total": 12.7,
       "device_wait_seconds_total": 1.2, "data_wait_seconds_total": 5.0}
WANT = {"step_period_ms.train": 102.0,          # 2.04 s over 20 normal steps
        "slow_step_share.train": 3.0,           # 0.081 s of 2.7 s
        "step_dispatch_ms.train": 20.0,         # 0.54 s over 27 steps
        "h2d_wait_share.train": 2.0,            # 0.054 s of 2.7 s
        "device_wait_share.train": 70.0}        # (1.69 + 0.2) s of 2.7 s
DENOMINATOR = {"step_period_ms.train": "normal_steps_total",
               "step_dispatch_ms.train": "steps_total"}


def ev(c0=C0, c1=C1):
    return {"counters0": c0, "counters1": c1, "trace": {}}


def read(name, evidence):
    fn, args = M.metric_reader(name)
    return fn(evidence, **args)


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_metric_from_a_pair_of_snapshots(name):
    assert read(name, ev()) == pytest.approx(WANT[name])
    # a program without the counters: nothing, and nothing raised
    assert read(name, ev(c0=OLD, c1=OLD)) is None
    assert read(name, {}) is None
    # the denominator did not rise: nothing
    over = DENOMINATOR.get(name, "step_seconds_total")
    assert read(name, ev(c1=dict(C1, **{over: C0[over]}))) is None


def test_counters_share_with_one_and_with_two_numerators():
    one = dict(counters=["step_prologue_block_seconds_total"],
               over="step_seconds_total", scale=100.0)
    two = dict(one, counters=one["counters"] + ["device_wait_seconds_total"])
    assert counters_share.read(ev(), **one) == pytest.approx(100 * 1.69 / 2.7)
    assert counters_share.read(ev(), **two) == pytest.approx(70.0)
    assert counters_share.read(ev(), **dict(two, scale=1.0)) \
        == pytest.approx(0.7)
    # either numerator absent, or the denominator: nothing
    for gone in two["counters"] + [two["over"]]:
        c1 = {k: v for k, v in C1.items() if k != gone}
        assert counters_share.read(ev(c1=c1), **two) is None
    # a counter the window's first snapshot lacks counts from 0
    c0 = {k: v for k, v in C0.items() if k != "device_wait_seconds_total"}
    assert counters_share.read(ev(c0=c0), **two) \
        == pytest.approx(100 * (1.69 + 1.2) / 2.7)


def test_the_manifest_has_a_reader_for_each_and_every_cell_reports_them():
    man = M.manifest()
    entries = {m["name"]: m for m in man["per_layer"]}
    for name in WANT:
        m = entries[name]
        assert m["source"] == "program_counter"
        assert m["moves"] == "train_clips_per_s"
        assert all(len(str(v)) <= 200 for v in m.values())
        M.metric_reader(name)
    # by the code, a sequence cell's window has three steps before its
    # trace starts (a drain, a short one, a normal one), none of them
    # judged (PERF.md section 6c, PR 34): the two metrics of judged steps
    # list the image cells; the other three are every training cell's
    judged = {"step_period_ms.train", "slow_step_share.train"}
    for name in judged:
        assert entries[name]["workloads"] == \
            ["train_flagship_600", "train_b4_380"]
    for w in man["workloads"]:
        cell = M.Cell(w["name"], man)
        names = {m["name"] for m in cell.per_layer()}
        assert set(WANT) - names <= judged
        got = M.read_per_layer(cell, ev())
        for name in set(WANT) & names:
            assert got[name]["value"] == pytest.approx(WANT[name])
        # evidence of the parent's program: none of the five, none raised
        assert not set(WANT) & set(M.read_per_layer(cell, ev(OLD, OLD)))
