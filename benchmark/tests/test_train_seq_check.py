"""The family-blind sequence driver's comparison at a size a test run can
hold (the ten-layer granite-4.0-h schedule at d 64, 48-token rows, 512
vocabulary rows, on the CPU, float32 so that rounding does not blur it), the
counts of ``lib/flops_ssd.py`` against a hand count, and the new reader on
hand-made evidence.

* a sound run of ``drivers/train_seq.run`` (without its look for a chip)
  comes out correct, feeds whole documents and builds nothing in its window;
* the control -- the reference put in the program's place one precision
  lower (bfloat16 under this float32 configuration) -- comes out not correct;
* each planted fault comes out not correct: a state left unchanged, half of
  the row's targets left out, every chunk of the scan started from a zero
  state, the norm before the gate.
"""
import json
import os
import time

import pytest

from benchmark.drivers import train_seq as D
from benchmark.drivers.train_tokens import shift
from benchmark.lib import faults_granite4h as FAULTS
from benchmark.lib import flops_ssd as F
from benchmark.lib import manifest as M
from benchmark.metrics import ssd_roofline

MAN = M.load_json(os.path.join(M.BENCH, "tests", "tiny",
                               "BENCHMARK.tiny_seq.json"))
SEED = 3000000019                       # past 2**31, as the driver's are
CELL = "train_granite4h_long"


def _cell():
    return M.Cell("train_tiny_seq", MAN)


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


def test_the_driver_names_no_family_and_finds_the_faults_by_the_config():
    assert D.model_faults(_cell().config) is FAULTS
    src = open(D.__file__).read()
    for word in ("granite", "phi4", "mamba", "ssd_scan"):
        assert word not in src.lower().replace("ssd_chunks", ""), word


@pytest.mark.parametrize("fault", D.STEP_FAULTS + FAULTS.MODEL_FAULTS)
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
    batches = [(dataset.pool[i:i + 1], shift(dataset.pool[i:i + 1]))
               for i in range(D.CHECK_STEPS)]
    ref = D.reference_first_steps(cell.config, spec, params0, batches)
    ctl = D.reference_first_steps(cell.config, spec, params0, batches,
                                  quant=cell.config["reference"]["control"])
    limits = cell.config["reference"]["limits"]
    assert D.judge(D.compare(ref, ref), limits)[0] is True
    ok, compared = D.judge(D.compare(ctl, ref), limits)
    assert ok is False, compared


def test_the_cells_traffic_is_the_issues_and_its_trace_plan_fits():
    cell = M.Cell(CELL)
    mix, steps = cell.traffic, int(cell.config["train"]["steps_per_epoch"])
    assert {k: mix[k] for k in ("driver", "pool_rows", "zipf_s",
                                "trace_epoch", "trace_from_step",
                                "trace_steps", "trace_prime_steps")} == {
        "driver": "train_seq", "pool_rows": 64, "zipf_s": 1.0,
        "trace_epoch": 1, "trace_from_step": 3, "trace_steps": 6,
        "trace_prime_steps": 5}
    at = int(mix["trace_from_step"])
    assert at >= D.CHECK_STEPS
    assert at + int(mix["trace_prime_steps"]) <= steps
    assert at + int(mix["trace_steps"]) <= steps - 1
    assert cell.chips == 1 and cell.driver() is D


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` is in the file under the
    same key, but the two under ``reduced``; nested groups are whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "granite-4.0-h-micro")
    config = M.Cell(CELL).config
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 10
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]


def test_committed_limits_separate_the_recorded_readings():
    """``readings/granite4_h_micro_10l.jsonl``: what the chip read at the
    cell's own size (``calibrate_seq.py`` and the cell's runs; PERF.md
    section 6 gives the ranges).  Under the committed limits every sound
    run is correct and the float8 control and each planted fault are not."""
    limits = M.Cell(CELL).config["reference"]["limits"]
    path = os.path.join(M.BENCH, "tests", "readings",
                        "granite4_h_micro_10l.jsonl")
    counts = {}
    for r in (json.loads(line) for line in open(path) if line.strip()):
        # a control is read through ``compare`` alone: no batch numbers
        ok, compared = D.judge(r["numbers"], limits,
                               every_limit=r["kind"] == "program")
        assert ok is (r["kind"] == "program"), (r["kind"], r["seed"],
                                                compared)
        if r["kind"] == "control":
            # each number the control reads three times the sound runs'
            # largest is held against it: it fails by both, not by one
            for k in ("grad1_err_median", "delta_median_gap"):
                assert compared[k]["value"] > compared[k]["limit"], (
                    k, r["seed"], compared[k])
        counts[r["kind"]] = counts.get(r["kind"], 0) + 1
    assert counts["program"] >= 5 and counts["control"] >= 1, counts
    assert set(D.STEP_FAULTS + FAULTS.MODEL_FAULTS) <= set(counts), counts


# ---------------------------------------------------------------------------
# operations and bytes from shapes
# ---------------------------------------------------------------------------

def test_counts_agree_with_a_hand_count_at_a_tiny_size():
    """d 8, 4 query heads to 1 KV head of 2, FFN 16, 2 scan heads of 8
    (inner 16), N 4, chunks of 3, 10 rows; one row of 5 positions through a
    Mamba-2 and an attention layer."""
    spec = {"d": 8, "heads": 4, "kv_heads": 1, "dh": 2, "ff": 16,
            "inner": 16, "ssm_heads": 2, "n": 4, "chunk": 3, "rows": 10}
    c = F.counts_for(spec, ("mamba", "attention"), 5)
    assert c["mlp"] == 2 * (2 * 5 * 8 * 32 + 2 * 5 * 16 * 8)
    # in_proj to z (16), xBC (16 + 8), dt (2); out_proj
    assert c["ssd_proj"] == 2 * 5 * (8 * 42 + 16 * 8)
    # chunks of 3 over 5 positions: 1 + 2 + 3 and 1 + 2 pairs
    assert F.chunk_pairs(5, 3) == 9 and F.chunk_pairs(6, 3) == 12
    assert F.chunk_pairs(2, 3) == 3
    # a pair: C.B once (2 x 4) and the product with x (2 x 16); a position:
    # the chunk's own state and the readout, 2 x 16 x 4 each
    assert c["ssd_scan"] == 9 * (2 * 4 + 2 * 16) + 5 * 2 * (2 * 16 * 4)
    # forward x, B, C, dt in and y out; backward the same in, dy besides,
    # and four gradients out
    assert c["ssd_elems"] == 5 * ((16 + 4 + 4 + 2 + 16) * 2
                                  + (16 + 4 + 4 + 2))
    # q (8 wide), k and v (2 each), out; a causal pair 2 x 2 + 2 x 2 a head
    assert c["attn_full"] == 2 * 5 * (8 * 12 + 8 * 8) + 8 * 4 * 15
    assert c["head"] == 2 * 5 * 8 * 10
    assert c["forward_flops"] == sum(
        c[k] for k in ("mlp", "ssd_proj", "ssd_scan", "attn_full", "head"))


def test_the_cells_counts_are_the_issues_figures():
    """ISSUE 30, section 6: per token forward the Mamba-2 projections 2 x
    25,821,184, the scan 128 x 257 + 4096 x 257 + 2 x 1,048,576, the MLP 2 x
    50,331,648, the attention projections 2 x 10,485,760 and 8,192 a causal
    pair; 1.64 GFLOP a token, 26.9 TFLOP a row, 80.6 TFLOP a step; the
    scan's floor 0.86 ms a layer of bytes against 0.79 ms of operations."""
    config = M.Cell(CELL).config
    from benchmark import reference
    counts = reference.model(config).forward_counts(config)
    l = 16384
    assert counts["ssd_proj"] == 9 * l * 2 * 25_821_184
    assert counts["ssd_scan"] == 9 * l * (128 * 257 + 4096 * 257
                                          + 2 * 1_048_576)
    assert counts["mlp"] == 10 * l * 2 * 50_331_648
    assert counts["attn_full"] == l * 2 * 10_485_760 \
        + 8192 * l * (l + 1) // 2
    assert counts["head"] == 2 * l * 2048 * 12544
    assert counts["forward_flops"] / l == pytest.approx(1.64e9, rel=2e-3)
    assert counts["forward_flops"] == pytest.approx(26.9e12, rel=2e-3)
    assert 3 * counts["forward_flops"] == pytest.approx(80.6e12, rel=1e-3)
    assert counts["ssd_elems"] == 9 * l * 21_440
    peak = M.load_json(os.path.join(M.BENCH, "lib", "peaks.json"))[
        "TPU v5 lite"]
    floor = F.scan_train_floor_seconds(counts, 1, peak)
    assert floor["bound"] == "bytes"
    assert floor["t_bytes"] / 9 == pytest.approx(0.86e-3, rel=5e-3)
    assert floor["t_flops"] / 9 == pytest.approx(0.79e-3, rel=1e-2)


# ---------------------------------------------------------------------------
# the new reader and the manifest
# ---------------------------------------------------------------------------

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
COUNTS = {"forward_flops": 70.0, "attn_full": 20.0, "ssd_scan": 1.0,
          "ssd_elems": 4.0}
RED = {"busy_s": 12.0, "by_group": {"attn_full": 2.0, "ssd_scan": 3.2,
                                   "ssd_proj": 1.0, "mlp": 2.0}}


def ev(**over):
    e = {"peak": PEAK, "trace": RED, "flop_counts": COUNTS,
         "traced": {"rows": 2, "wall_s": 9.0},
         "mode": "train", "chips": 1,
         "counters0": {"steps_total": 10.0, "ssd_chunks_total": 5760.0,
                       "step_seconds_total": 1.0,
                       "data_wait_seconds_total": 0.1},
         "counters1": {"steps_total": 14.0, "ssd_chunks_total": 8064.0,
                       "step_seconds_total": 2.0,
                       "data_wait_seconds_total": 0.2}}
    e.update(over)
    return e


def test_scan_roofline_takes_the_larger_of_operations_and_bytes():
    # bytes: 4 elements x 2 rows x 2 bytes / 10 bytes/s = 1.6 s over 3.2 s
    assert ssd_roofline.read(ev()) == pytest.approx(50.0)
    # operations: 3 x 40 x 2 rows / 100 = 2.4 s over 3.2 s
    assert ssd_roofline.read(ev(flop_counts=dict(COUNTS, ssd_scan=40.0))) \
        == pytest.approx(75.0)
    # a parent without the scope, another family's counts: nothing, never 0
    assert ssd_roofline.read(ev(traced=None)) is None
    assert ssd_roofline.read(ev(flop_counts={"scan_elems": 1.0})) is None
    assert ssd_roofline.read(
        ev(trace=dict(RED, by_group={"mlp": 2.0}))) is None
    assert ssd_roofline.read({}) is None


def test_the_manifest_finds_the_cells_nine_metrics():
    cell = M.Cell(CELL)
    names = {m["name"] for m in cell.per_layer()}
    new = {"ssd_roofline.train", "attn_gqa_roofline.train",
           "ssd_mixer_share.train", "ssd_chunks_per_step.train"}
    assert names == new | {"data_wait_share.train", "host_wait_share.train",
                           "step_mfu.train", "step_device_ms.train",
                           "device_idle_share.train"}
    for old in ("train_flagship_600", "train_b4_380", "train_phi4flash_long"):
        assert not new & {m["name"] for m in M.Cell(old).per_layer()}
    out = M.read_per_layer(cell, ev())
    assert out["ssd_chunks_per_step.train"]["value"] == 576.0
    assert out["attn_gqa_roofline.train"]["value"] == \
        pytest.approx(100.0 * 3 * 20 * 2 / 100.0 / 2.0)
    assert out["ssd_mixer_share.train"]["value"] == \
        pytest.approx(100.0 * 6.2 / 12.0)
    # a program without the counter or the scopes (the parent): left out
    old_counters = {"steps_total": 14.0, "step_seconds_total": 2.0,
                    "data_wait_seconds_total": 0.2}
    bare = M.read_per_layer(cell, ev(
        counters0=old_counters, counters1=old_counters,
        trace=dict(RED, by_group={"mlp": 2.0})))
    assert not new & set(bare)
