"""The family-blind sequence driver on a configuration with routed experts
held in part, at a size a test run can hold (the five-layer LFM2 cut at d 64,
8 experts top-2 of which two are held, 48-token rows, batch 2 x grad-accum 2
so that the accumulation path is under test, on the CPU, float32), the counts
of ``lib/flops_moe.py`` against a hand count, and the new reader on hand-made
evidence.

* a sound run of ``drivers/train_seq.run`` (without its look for a chip)
  comes out correct, feeds whole documents and builds nothing in its window;
* the control -- the reference put in the program's place one precision
  lower (bfloat16 under this float32 configuration) -- comes out not correct;
* each planted fault comes out not correct: a state left unchanged, half of
  the rows' targets left out, the bias weighing, the weights normalised over
  the held experts only, no rotation, the gate after the convolution.
"""
import json
import os
import time

import pytest

from benchmark.drivers import train_seq as D
from benchmark.drivers.train_tokens import shift
from benchmark.lib import faults_lfm2moe as FAULTS
from benchmark.lib import flops_moe as F
from benchmark.lib import manifest as M
from benchmark.metrics import moe_roofline

MAN = M.load_json(os.path.join(M.BENCH, "tests", "tiny",
                               "BENCHMARK.tiny_moe.json"))
SEED = 3000000019                       # past 2**31, as the driver's are
CELL = "train_lfm2moe_8k"


def _cell():
    return M.Cell("train_tiny_moe", MAN)


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
    # 2 rows a microbatch x 2 microbatches: the accumulation path
    assert w["batch"] == 4 and w["steps_per_epoch"] == 4
    assert w["tokens_per_s"] == pytest.approx(
        w["rows_per_s"] * int(cell.config["train"]["seq_len"]))


def test_the_driver_finds_the_faults_by_the_config():
    assert D.model_faults(_cell().config) is FAULTS
    assert len(FAULTS.MODEL_FAULTS) == 4


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
    dataset, variables, spec = D.make_inputs(cell, 7, 4)
    params0 = jax.device_get(variables["params"])
    batches = [(dataset.pool[4 * i:4 * i + 4],
                shift(dataset.pool[4 * i:4 * i + 4]))
               for i in range(D.CHECK_STEPS)]
    ref = D.reference_first_steps(cell.config, spec, params0, batches)
    ctl = D.reference_first_steps(cell.config, spec, params0, batches,
                                  quant=cell.config["reference"]["control"])
    limits = cell.config["reference"]["limits"]
    assert D.judge(D.compare(ref, ref), limits)[0] is True
    ok, compared = D.judge(D.compare(ctl, ref), limits)
    assert ok is False, compared


def test_the_cells_traffic_is_the_accepted_mix_and_its_trace_plan_fits():
    cell = M.Cell(CELL)
    mix, steps = cell.traffic, int(cell.config["train"]["steps_per_epoch"])
    assert cell.entry["traffic"] == "train_docs_long"
    at = int(mix["trace_from_step"])
    assert at >= D.CHECK_STEPS
    assert at + int(mix["trace_prime_steps"]) <= steps
    assert at + int(mix["trace_steps"]) <= steps - 1
    assert cell.chips == 1 and cell.driver() is D
    train = cell.config["train"]
    flags = cell.config["train_flags"]
    b, a = (int(flags[flags.index(k) + 1]) for k in ("-b", "--grad-accum"))
    assert b * a == train["batch"] == 8 and a == train["grad_accum"]
    assert train["batch"] * train["seq_len"] == 65536
    assert int(flags[flags.index("--seq-len") + 1]) == train["seq_len"]


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` is in the file under the
    same key, but the four under ``reduced``; nested groups are whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "LFM2-24B-A2B")
    config = M.Cell(CELL).config
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert sorted(config["reduced_detail"]) == sorted(config["reduced"])
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (5, 1)
    assert config["num_experts"] * 8 == config["num_experts_published"] \
        == row["config"]["num_experts"]
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "conv_L_cache"):
        assert key not in config["reduced"]


def test_committed_limits_separate_the_recorded_readings():
    """``readings/lfm2_24b_a2b_5l.jsonl``: what the chip read at the cell's
    own size (``calibrate_seq.py`` and the cell's runs; PERF.md section 6
    gives the ranges).  Under the committed limits every sound run is
    correct and the float8 control and every planted fault are not."""
    limits = M.Cell(CELL).config["reference"]["limits"]
    path = os.path.join(M.BENCH, "tests", "readings",
                        "lfm2_24b_a2b_5l.jsonl")
    counts, by_loss = {}, 0
    for r in (json.loads(line) for line in open(path) if line.strip()):
        # a control is read through ``compare`` alone: no batch numbers
        ok, compared = D.judge(r["numbers"], limits,
                               every_limit=r["kind"] == "program")
        assert ok is (r["kind"] == "program"), (r["kind"], r["seed"],
                                                compared)
        over = {k for k, c in compared.items() if c["value"] > c["limit"]}
        if r["kind"] == "control":
            # by both numbers that separate it on every seed, not by one;
            # by the loss on five seeds of six (one control reads 3.4e-5,
            # a sound run's loss)
            assert over >= {"grad1_err_median", "delta_median_gap"}, (
                r["seed"], compared)
            by_loss += "loss_gap" in over
        if r["kind"] == "bias_weighs":
            # a hundredth on a weight, a quarter of the program's own
            # selection noise: the worst leaf's gradient norm alone sees it
            assert over == {"grad1_gap"}, (r["seed"], compared)
        counts[r["kind"]] = counts.get(r["kind"], 0) + 1
    assert counts["program"] >= 5 and counts["control"] >= 2, counts
    assert by_loss >= counts["control"] - 1
    assert set(D.STEP_FAULTS + FAULTS.MODEL_FAULTS) <= set(counts), counts
    text = M.Cell(CELL).config["reference"]["readings"]
    for name in set(limits) - {"batch_gap", "target_gap"}:
        assert name in text
    assert "bias_weighs" in text


# ---------------------------------------------------------------------------
# operations and bytes from shapes
# ---------------------------------------------------------------------------

def test_counts_agree_with_a_hand_count_at_a_tiny_size():
    """d 8, 4 query heads to 1 KV head of 2, dense width 12, experts of 6,
    top-2 of 8 with 2 held, 10 rows; one row of 5 positions through a dense
    conv layer, an attention layer with experts and a conv layer with
    experts."""
    spec = {"d": 8, "heads": 4, "kv_heads": 1, "dh": 2, "ff": 12, "f": 6,
            "experts": 8, "held": (2, 2), "top_k": 2, "rows": 10}
    c = F.counts_for(spec, (("conv", True), ("full_attention", False),
                            ("conv", False)), 5)
    # in_proj to B, C, u and out_proj, twice
    assert c["conv_mix"] == 2 * (2 * 5 * 8 * 24 + 2 * 5 * 8 * 8)
    # q (8 wide), k and v (2 each), out; a causal pair 2 x 2 + 2 x 2 a head
    assert c["attn_full"] == 2 * 5 * (8 * 12 + 8 * 8) + 8 * 4 * 15
    assert c["mlp_dense"] == 2 * 5 * 8 * 24 + 2 * 5 * 12 * 8
    assert c["moe_router"] == 2 * (2 * 5 * 8 * 8)
    # an assignment: three products of 8 x 6; a token brings 2 x 2 / 8
    assert c["moe_assignment_flops"] == 2 * 3 * 8 * 6
    assert c["moe_experts"] == 2 * 5 * 0.5 * (2 * 3 * 8 * 6)
    assert c["moe_tokens"] == 2 * 5
    assert c["moe_weight_elems"] == 2 * 2 * 3 * 8 * 6
    assert c["moe_row_elems"] == 2 * 8
    assert c["head"] == 2 * 5 * 8 * 10
    assert c["forward_flops"] == sum(c[k] for k in (
        "conv_mix", "attn_full", "mlp_dense", "moe_router", "moe_experts",
        "head"))


def test_the_cells_counts_are_the_issues_figures():
    """ISSUE 32: needed work 73 TFLOP of products and 6.6 of attention pairs
    a step of 8 rows; the experts' floor 37.7 ms a step at the uniform share
    of 0.5 assignments a routed token."""
    config = M.Cell(CELL).config
    from benchmark import reference
    counts = reference.model(config).forward_counts(config)
    l = 8192
    pairs = 4 * 64 * 32 * l * (l + 1) // 2
    assert counts["conv_mix"] == 4 * 2 * l * 2048 * (6144 + 2048)
    assert counts["attn_full"] == 2 * l * 2048 * (3072 + 2048) + pairs
    assert counts["mlp_dense"] == 2 * l * 3 * 2048 * 11776
    assert counts["moe_router"] == 4 * 2 * l * 2048 * 64
    assert counts["moe_experts"] == 4 * l * 0.5 * 2 * 3 * 2048 * 1536
    assert counts["head"] == 2 * l * 2048 * 8192
    step = 3 * 8 * counts["forward_flops"]
    assert 3 * 8 * pairs == pytest.approx(6.6e12, rel=1e-2)
    assert step - 3 * 8 * pairs == pytest.approx(73e12, rel=1e-2)
    assert counts["moe_tokens"] == 4 * l
    assert counts["moe_weight_elems"] == 4 * 8 * 3 * 2048 * 1536
    peak = M.load_json(os.path.join(M.BENCH, "lib", "peaks.json"))[
        "TPU v5 lite"]
    floor = F.experts_train_floor_seconds(
        counts, 0.5 * counts["moe_tokens"] * 8, 1, peak)
    assert floor["bound"] == "flops"
    assert floor["seconds"] == pytest.approx(37.7e-3, rel=2e-3)
    assert floor["t_bytes"] == pytest.approx(6.1e-3, rel=2e-2)


# ---------------------------------------------------------------------------
# the new reader and the manifest
# ---------------------------------------------------------------------------

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
COUNTS = {"forward_flops": 70.0, "attn_full": 20.0, "moe_tokens": 10.0,
          "moe_assignment_flops": 4.0, "moe_weight_elems": 1.0,
          "moe_row_elems": 0.5}
RED = {"busy_s": 12.0, "steps": 1,
       "by_group": {"attn_full": 2.0, "moe_experts": 1.2, "moe_router": 0.3,
                    "moe_dispatch": 0.2, "moe_combine": 0.1, "mlp_dense": 2.0}}


def ev(**over):
    e = {"peak": PEAK, "trace": RED, "flop_counts": COUNTS,
         "traced": {"rows": 2, "wall_s": 9.0},
         "mode": "train", "chips": 1,
         "counters0": {"steps_total": 10.0, "step_seconds_total": 1.0,
                       "data_wait_seconds_total": 0.1,
                       "moe_routed_tokens_total": 400.0,
                       "moe_assignments_total": 190.0,
                       "moe_full_capacity_passes_total": 1.0},
         "counters1": {"steps_total": 11.0, "step_seconds_total": 2.0,
                       "data_wait_seconds_total": 0.2,
                       "moe_routed_tokens_total": 440.0,
                       "moe_assignments_total": 210.0,
                       "moe_full_capacity_passes_total": 3.0}}
    e.update(over)
    return e


def test_expert_roofline_prices_the_assignments_the_counters_report():
    # 20 of 40 routed tokens' assignments rose: 0.5 a token; 10 tokens a
    # row x 2 rows = 10 assignments; 3 x 10 x 4 / 100 = 1.2 s of operations,
    # 3 x (1 + 10 x 0.5) x 2 / 10 = 3.6 s of bytes: the larger, over 1.2 s
    assert moe_roofline.assignments_per_token(
        ev()["counters0"], ev()["counters1"]) == 0.5
    assert moe_roofline.read(ev()) == pytest.approx(100.0 * 3.6 / 1.2)
    fast = dict(PEAK, hbm_bytes_per_s=1e6)
    # a group that takes exactly its floor reads 100 and no more
    assert moe_roofline.read(ev(peak=fast)) == pytest.approx(100.0)
    # twice the assignments, twice the floor: it follows the routing that ran
    more = dict(ev()["counters1"], moe_assignments_total=230.0)
    assert moe_roofline.read(ev(peak=fast, counters1=more)) == \
        pytest.approx(200.0)
    # nothing rose between the two snapshots: the totals themselves
    same = ev()["counters1"]
    assert moe_roofline.assignments_per_token(same, same) == \
        pytest.approx(210.0 / 440.0)
    # a parent without the counters or the scope, another family's counts:
    # nothing, never 0
    bare = {"steps_total": 11.0}
    assert moe_roofline.read(ev(counters0=bare, counters1=bare)) is None
    assert moe_roofline.read(ev(counters1=None)) is None
    assert moe_roofline.read(ev(traced=None)) is None
    assert moe_roofline.read(ev(flop_counts={"ssd_elems": 1.0})) is None
    assert moe_roofline.read(
        ev(trace=dict(RED, by_group={"mlp_dense": 2.0}))) is None
    assert moe_roofline.read({}) is None


def test_the_manifest_finds_the_cells_ten_metrics():
    cell = M.Cell(CELL)
    names = {m["name"] for m in cell.per_layer()}
    new = {"moe_expert_roofline.train", "moe_share.train",
           "attn_rope_roofline.train", "moe_assignments_per_token.train",
           "moe_full_capacity_passes.train"}
    assert names == new | {"data_wait_share.train", "host_wait_share.train",
                           "step_mfu.train", "step_device_ms.train",
                           "device_idle_share.train"}
    for old in ("train_flagship_600", "train_b4_380", "train_phi4flash_long",
                "train_granite4h_long"):
        assert not new & {m["name"] for m in M.Cell(old).per_layer()}
    out = M.read_per_layer(cell, ev())
    assert out["moe_assignments_per_token.train"]["value"] == 0.5
    assert out["moe_full_capacity_passes.train"]["value"] == 2.0
    assert out["moe_share.train"]["value"] == pytest.approx(100 * 1.8 / 12.0)
    assert out["attn_rope_roofline.train"]["value"] == \
        pytest.approx(100.0 * 3 * 20 * 2 / 100.0 / 2.0)
    assert out["moe_expert_roofline.train"]["value"] == pytest.approx(300.0)
    # a program without the counters or the scopes (the parent): left out
    old_counters = {"steps_total": 14.0, "step_seconds_total": 2.0,
                    "data_wait_seconds_total": 0.2}
    bare = M.read_per_layer(cell, ev(
        counters0=old_counters, counters1=old_counters,
        trace=dict(RED, by_group={"mlp_dense": 2.0})))
    assert not new & set(bare)


def test_the_entries_this_pr_appended_keep_the_manifests_form():
    """The driver refuses the file before any run for a line over 200
    characters (it refused this PR's first configuration ``why`` of 211)."""
    cell = M.Cell(CELL)
    root = os.path.join(os.path.dirname(__file__), "..", "..")
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell.entry["config"])
    for line in (config["why"], config["source"], cell.entry["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable() and line.isascii()
    assert manifest["configs"][-1] is config
    assert manifest["workloads"][-1] == cell.entry
