"""The sequence driver's comparison at a size a test run can hold (the
six-layer SambaY schedule at d 64, 48-token rows, 512 vocabulary rows, on
the CPU, float32 so that rounding does not blur it), the counts of
``lib/flops_seq.py`` against a hand count, and the new readers on hand-made
evidence.

* a sound run of ``drivers/train_tokens.run`` (without its look for a chip)
  comes out correct, feeds whole documents and builds nothing in its window;
* the control -- the reference put in the program's place one precision
  lower (bfloat16 under this float32 configuration) -- comes out not correct;
* each planted fault comes out not correct: a state left unchanged, half of
  the row's targets left out, the window ignored, the memory taken from the
  wrong layer.
"""
import os
import time

import pytest

from benchmark.drivers import train_tokens as D
from benchmark.lib import flops_seq as F
from benchmark.lib import manifest as M
from benchmark.metrics import attn_roofline, group_share, ssm_scan_roofline

MAN = M.load_json(os.path.join(M.BENCH, "tests", "tiny",
                               "BENCHMARK.tiny_tokens.json"))
SEED = 3000000019                       # past 2**31, as the driver's are


def _cell():
    return M.Cell("train_tiny_tokens", MAN)


def _run(fault=None, seed=SEED):
    return D.run(_cell(), seed, 2.0, False, time.time(), need_chip=False,
                 fault=fault)


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_sound_run_is_correct_and_builds_nothing_in_its_window(sound):
    cell = _cell()
    assert sound["correct"] is True
    assert set(sound["compared"]) == set(cell.config["reference"]["limits"])
    for c in sound["compared"].values():
        assert 0 <= c["value"] <= c["limit"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_clips_per_s", "setup_s"}
    w = sound["window"]
    assert w["compiles"] == 0
    assert w["batch"] == 1 and w["steps_per_epoch"] == 4
    assert w["tokens_per_s"] == pytest.approx(
        w["rows_per_s"] * int(cell.config["train"]["seq_len"]))


@pytest.mark.parametrize("fault", D.STEP_FAULTS + D.MODEL_FAULTS)
def test_planted_fault_is_not_correct(fault):
    res = _run(fault=fault)
    assert res["correct"] is False
    assert [k for k, c in res["compared"].items()
            if not c["value"] <= c["limit"]], res["compared"]


def test_control_one_precision_lower_is_not_correct():
    """The control needs no window: the reference in bfloat16 against the
    reference, on a pool's first rows and the seeded weights."""
    import jax
    cell = _cell()
    dataset, variables, spec = D.make_inputs(cell, 7, 1)
    params0 = jax.device_get(variables["params"])
    batches = [(dataset.pool[i:i + 1], D.shift(dataset.pool[i:i + 1]))
               for i in range(D.CHECK_STEPS)]
    ref = D.reference_first_steps(cell.config, spec, params0, batches)
    ctl = D.reference_first_steps(cell.config, spec, params0, batches,
                                  quant=cell.config["reference"]["control"])
    limits = cell.config["reference"]["limits"]
    assert D.judge(D.compare(ref, ref), limits)[0] is True
    ok, compared = D.judge(D.compare(ctl, ref), limits)
    assert ok is False, compared


def test_the_cells_trace_plan_starts_after_the_taps_and_ends_in_its_epoch():
    """``StepTap`` looks for a session's first launch only from its fourth
    call on, and a session that never starts blocks the next: the priming
    must start at call 3 or later and end before the window's session
    starts, which must end inside its epoch (no drain in the span)."""
    cell = M.Cell("train_phi4flash_long")
    mix, steps = cell.traffic, int(cell.config["train"]["steps_per_epoch"])
    at = int(mix["trace_from_step"])
    assert at >= D.CHECK_STEPS
    assert at + int(mix["trace_prime_steps"]) <= steps
    assert at + int(mix["trace_steps"]) <= steps - 1
    assert int(mix["trace_epoch"]) >= 1 and int(mix["trace_steps"]) >= 4


def test_pool_is_seeded_zipf_over_the_rows_held():
    a = D.make_pool(SEED, 8, 4096, 512, 1.0)
    assert (a == D.make_pool(SEED, 8, 4096, 512, 1.0)).all()
    assert (a != D.make_pool(SEED + 1, 8, 4096, 512, 1.0)).any()
    assert a.dtype.name == "int32" and a.min() == 0 and a.max() < 512
    # P(0) = 1 / H_512 = 0.146: the head of the law is where it should be
    assert abs((a == 0).mean() - 0.1463) < 0.01


def test_committed_limits_separate_the_recorded_readings():
    """``readings/phi4_mini_flash_6l.jsonl``: what the chip read at the
    cell's own size (``calibrate_tokens.py`` and the cell's runs; PERF.md
    section 6 gives the ranges).  Under the committed limits every sound
    run is correct and the float8 control and each planted fault are not."""
    import json
    limits = M.Cell("train_phi4flash_long").config["reference"]["limits"]
    path = os.path.join(M.BENCH, "tests", "readings",
                        "phi4_mini_flash_6l.jsonl")
    counts = {}
    for r in (json.loads(line) for line in open(path) if line.strip()):
        # a control is read through ``compare`` alone: no batch numbers
        ok, compared = D.judge(r["numbers"], limits,
                               every_limit=r["kind"] == "program")
        assert ok is (r["kind"] == "program"), (r["kind"], r["seed"],
                                                compared)
        counts[r["kind"]] = counts.get(r["kind"], 0) + 1
    assert counts["program"] >= 3 and counts["control"] >= 3, counts
    assert set(D.STEP_FAULTS + D.MODEL_FAULTS) <= set(counts), counts


# ---------------------------------------------------------------------------
# operations and bytes from shapes
# ---------------------------------------------------------------------------

def test_counts_agree_with_a_hand_count_at_a_tiny_size():
    """d 8, 4 query / 2 KV heads of 2, FFN 16, inner 16, N 4, R 2, window 3,
    10 rows, one row of 5 positions through all five kinds."""
    spec = {"d": 8, "heads": 4, "kv_heads": 2, "dh": 2, "ff": 16,
            "inner": 16, "n": 4, "rank": 2, "window": 3, "rows": 10}
    c = F.counts_for(spec, ("mamba", "window", "full", "gmu", "cross"), 5)
    assert c["mlp"] == 5 * (2 * 5 * 8 * 32 + 2 * 5 * 16 * 8)
    assert c["mamba_proj"] == 2 * 5 * (8 * 32 + 16 * 10 + 2 * 16 + 16 * 8)
    assert c["mamba_scan"] == 6 * 5 * 16 * 4 + 2 * 5 * 16
    assert c["scan_elems"] == 8 * 5 * 16 + 6 * 5 * 4
    assert c["gmu"] == 2 * 5 * (8 * 16 + 16 * 8)
    # a pair: score 2*2 and values 2*4 a map, two maps, two query pairs
    pair = (2 * 2 + 2 * 4) * 2 * 2
    # window 3 over 5 positions: 1 + 2 + 3 + 3 + 3 pairs; causal: 15
    assert F.window_pairs(5, 3) == 12 and F.causal_pairs(5) == 15
    assert F.window_pairs(2, 3) == F.causal_pairs(2) == 3
    proj = 2 * 5 * (8 * 16 + 8 * 8)               # qkv (16 wide) and out
    assert c["attn_window"] == proj + pair * 12
    assert c["attn_full"] == proj + pair * 15
    assert c["attn_cross"] == 2 * 5 * (8 * 8 + 8 * 8) + pair * 15
    assert c["head"] == 2 * 5 * 8 * 10
    assert c["forward_flops"] == sum(
        c[k] for k in ("mlp", "mamba_proj", "mamba_scan", "gmu",
                       "attn_window", "attn_full", "attn_cross", "head"))


def test_the_cells_counts_and_the_scans_floor():
    """The cell as BENCHMARK.json has it: the window layer's pairs are 1/32
    of a causal layer's, and bytes bind the scan."""
    counts = F.forward_counts(M.Cell("train_phi4flash_long").config)
    l = 16384
    pair = 15360.0         # (2 x 64 + 2 x 128) x 2 maps x 20 query pairs
    assert counts["attn_full"] - counts["attn_cross"] == \
        2.0 * l * 2560 * 2560                      # W_k and W_v
    proj = 2.0 * l * 2560 * (5120 + 2560)
    assert counts["attn_full"] == proj + pair * l * (l + 1) / 2
    assert counts["attn_window"] == proj + pair * (512 * 513 / 2
                                                   + (l - 512) * 512)
    assert 2.6e13 < counts["forward_flops"] < 2.8e13
    peak = M.load_json(os.path.join(M.BENCH, "lib", "peaks.json"))[
        "TPU v5 lite"]
    floor = F.scan_train_floor_seconds(counts, 1, peak)
    assert floor["bound"] == "bytes"
    assert floor["seconds"] == pytest.approx(
        2 * (8 * l * 5120 + 6 * l * 16) * 2 / peak["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# the new readers, on hand-made evidence
# ---------------------------------------------------------------------------

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
COUNTS = {"forward_flops": 70.0, "attn_window": 10.0, "attn_full": 20.0, "attn_cross": 30.0,
          "mamba_scan": 1.0, "scan_elems": 4.0}
RED = {"busy_s": 12.0, "by_group": {"attn_window": 1.0, "attn_full": 2.0,
                                   "attn_cross": 2.0, "mamba_scan": 3.2,
                                   "mlp": 2.0}}


def ev(**over):
    e = {"peak": PEAK, "trace": RED, "flop_counts": COUNTS,
         "traced": {"rows": 2, "wall_s": 9.0},
         "mode": "train", "chips": 1,
         "counters0": {"steps_total": 10.0, "train_tokens_total": 480.0,
                       "step_seconds_total": 1.0,
                       "data_wait_seconds_total": 0.1},
         "counters1": {"steps_total": 14.0, "train_tokens_total": 672.0,
                       "step_seconds_total": 2.0,
                       "data_wait_seconds_total": 0.2}}
    e.update(over)
    return e


def test_attention_rooflines():
    # 3 passes x operations x 2 rows at peak, over the groups' device time
    assert attn_roofline.read(ev(), groups=["attn_window"]) == \
        pytest.approx(100.0 * 3 * 10 * 2 / 100.0 / 1.0)
    assert attn_roofline.read(ev(), groups=["attn_full", "attn_cross"]) == \
        pytest.approx(100.0 * 3 * 50 * 2 / 100.0 / 4.0)
    # a parent without the scopes, a trace without the group, another
    # family's counts: nothing, never 0
    assert attn_roofline.read(ev(trace={}), groups=["attn_window"]) is None
    assert attn_roofline.read(ev(trace=dict(RED, by_group={"mlp": 2.0})),
                              groups=["attn_window"]) is None
    assert attn_roofline.read(ev(flop_counts={"dense_flops": 1.0}),
                              groups=["attn_window"]) is None
    assert attn_roofline.read({}, groups=["attn_window"]) is None


def test_scan_roofline_is_bound_by_bytes():
    # 4 elements x 2 rows x 2 bytes / 10 bytes/s = 1.6 s over 3.2 s
    assert ssm_scan_roofline.read(ev()) == pytest.approx(50.0)
    assert ssm_scan_roofline.read(ev(traced=None)) is None
    assert ssm_scan_roofline.read(ev(flop_counts={"dw_flops": 1.0})) is None
    assert ssm_scan_roofline.read(
        ev(trace=dict(RED, by_group={"mlp": 2.0}))) is None


def test_group_share():
    groups = ["mamba_scan", "attn_window", "attn_full", "attn_cross", "gmu"]
    assert group_share.read(ev(), groups=groups) == \
        pytest.approx(100.0 * 8.2 / 12.0)
    assert group_share.read(ev(trace=None), groups=groups) is None
    assert group_share.read(ev(trace=dict(RED, by_group={"mlp": 2.0})),
                            groups=groups) is None


def test_the_manifest_finds_the_new_cells_metrics_and_tokens_per_step():
    cell = M.Cell("train_phi4flash_long")
    names = {m["name"] for m in cell.per_layer()}
    new = {"ssm_scan_roofline.train", "attn_window_roofline.train",
           "attn_global_roofline.train", "mixer_share.train",
           "tokens_per_step.train"}
    assert new <= names
    assert {"step_mfu.train", "step_device_ms.train",
            "device_idle_share.train", "data_wait_share.train",
            "host_wait_share.train"} <= names
    assert "conv_dw_roofline.train" not in names
    for old in ("train_flagship_600", "train_b4_380"):
        assert not new & {m["name"] for m in M.Cell(old).per_layer()}
    out = M.read_per_layer(cell, ev())
    assert out["tokens_per_step.train"]["value"] == 48.0
    # a program without the counter (the parent): the metric is left out
    old_counters = {"steps_total": 14.0, "step_seconds_total": 2.0,
                    "data_wait_seconds_total": 0.2}
    assert "tokens_per_step.train" not in M.read_per_layer(
        cell, ev(counters0=old_counters, counters1=old_counters))
