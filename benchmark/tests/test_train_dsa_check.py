"""``drivers/train_seq.py`` on a configuration with learned sparse attention
(an indexer selects each query's keys, its KL loss beside the next-token
loss) and a softmax router over routed experts held in part, at a size a
test run can hold (the four-layer Keye-VL-2.0 cut at d 64, 16 experts top-4
of which two are held, top-16 keys, 64-token rows, batch 2 x grad-accum 2,
on the CPU, float32), the counts of ``lib/flops_dsa.py`` against a hand
count, the cell's entries and the readings behind its limits.

* a sound run of ``drivers/train_seq.run`` (without its look for a chip)
  comes out correct, feeds whole documents and builds nothing in its window;
* the control -- the reference put in the program's place one precision
  lower (bfloat16 under this float32 configuration) -- comes out not correct;
* each planted fault comes out not correct: a state left unchanged, half of
  the rows' targets left out, every causal key attended, half the keys
  kept, the indexer's ReLU dropped, the indexer's input not held, a sigmoid
  router, QK-norm dropped.
"""
import json
import os
import time

import pytest

from benchmark.drivers import train_seq as D
from benchmark.drivers.train_tokens import shift
from benchmark.lib import faults_keyevl2 as FAULTS
from benchmark.lib import flops_dsa as F
from benchmark.lib import manifest as M

MAN = M.load_json(os.path.join(M.BENCH, "tests", "tiny",
                               "BENCHMARK.tiny_dsa.json"))
SEED = 3000000019                       # past 2**31, as a run's may be
CELL = "train_keye_dsa_32k"
NEW = {"attn_sparse_roofline.train", "dsa_index_roofline.train",
       "dsa_share.train", "dsa_block_fill.train"}


def _cell():
    return M.Cell("train_tiny_dsa", MAN)


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
    assert w["batch"] == 4 and w["steps_per_epoch"] == 4


def test_train_seq_finds_the_faults_by_the_config():
    assert D.model_faults(_cell().config) is FAULTS
    assert D.model_faults(M.Cell(CELL).config) is FAULTS
    assert len(FAULTS.MODEL_FAULTS) == 6


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
    assert b * a == train["batch"] == 1 and a == train["grad_accum"]
    assert train["seq_len"] == 32768
    assert int(flags[flags.index("--seq-len") + 1]) == train["seq_len"]
    assert mix["pool_rows"] == 64 and steps == 10


# the published config.json's numbers
# (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_the_configuration_keeps_every_published_number():
    """Every number of the published config is in the file under the same
    key, but the three under ``reduced``."""
    config = M.Cell(CELL).config
    assert config["source"] == ("https://huggingface.co/Kwai-Keye/"
                                "Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert set(config["reduced"]) <= set(config["reduced_detail"])
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 4
    assert config["num_experts"] * 8 == config["num_experts_published"] \
        == PUBLISHED["num_experts"]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "sa_config"):
        assert key not in config["reduced"]


def test_committed_limits_separate_the_recorded_readings():
    """``readings/keye_vl2_30b_a3b_4l.jsonl``: what the chip read at the
    cell's own size (``calibrate_seq.py`` and the cell's runs; PERF.md
    section 6 gives the ranges).  Under the committed limits every sound run
    is correct and the float8 control and every planted fault read are
    not."""
    limits = M.Cell(CELL).config["reference"]["limits"]
    path = os.path.join(M.BENCH, "tests", "readings",
                        "keye_vl2_30b_a3b_4l.jsonl")
    seeds = {}
    for r in (json.loads(line) for line in open(path) if line.strip()):
        ok, compared = D.judge(r["numbers"], limits,
                               every_limit=r["kind"] == "program")
        assert ok is (r["kind"] == "program"), (r["kind"], r["seed"],
                                                compared)
        seeds.setdefault(r["kind"], set()).add(r["seed"])
    assert len(seeds["program"]) >= 3, seeds
    for kind in ("control", "dense_attention", "topk_halved"):
        assert seeds.get(kind), (kind, seeds)
    text = M.Cell(CELL).config["reference"]["readings"]
    for name in set(limits) - {"batch_gap", "target_gap"}:
        assert name in text


# ---------------------------------------------------------------------------
# operations from shapes
# ---------------------------------------------------------------------------

def test_counts_agree_with_a_hand_count_at_a_tiny_size():
    """d 8, 4 query heads of 2 over 2 key heads, an indexer of 3 heads of
    2 keeping 2 keys, experts of 6, top-2 of 8 with 2 held, 10 rows; one
    row of 5 positions through one layer."""
    spec = {"d": 8, "heads": 4, "kv_heads": 2, "dh": 2, "index_heads": 3,
            "index_dim": 2, "topk": 2, "f": 6, "experts": 8, "held": (2, 2),
            "top_k": 2, "rows": 10, "layers": 1}
    c = F.counts_for(spec, 5)
    assert (c["causal_pairs"], c["selected_pairs"]) == (15, 1 + 2 + 2 + 2 + 2)
    assert c["attn_proj"] == 2 * 5 * (8 * 8 + 2 * 8 * 4 + 8 * 8)
    proj = 2 * 5 * (8 * 6 + 8 * 2 + 8 * 3)
    assert c["dsa_index"] == pytest.approx(2 / 3 * proj + 2 * 3 * 2 * 15 / 3)
    # a selected pair: 2 x 2 for the score, 2 x 2 for the value, 4 heads;
    # and the index score's backward, two thirds of a pass of 2 x 3 x 2
    assert c["attn_sparse"] == pytest.approx(
        4 * (2 * 2 + 2 * 2) * 9 + 2 * 3 * 2 * 2 * 9 / 3)
    assert c["moe_router"] == 2 * 5 * 8 * 8
    assert c["moe_experts"] == 5 * 0.5 * (2 * 3 * 8 * 6)
    assert (c["moe_tokens"], c["moe_weight_elems"]) == (5, 2 * 3 * 8 * 6)
    assert c["head"] == 2 * 5 * 8 * 10
    assert c["forward_flops"] == sum(c[k] for k in F.GROUPS)


def test_the_cells_counts_at_32768_tokens():
    """A row of 32,768 tokens: 65,012,736 selected of 536,887,296 causal
    pairs a layer (12.1%); per layer 1,237 GF of attention projections,
    148 GF of indexer projections, 1,100 GF of index scores over every
    causal pair, 1,065 GF of attention over the selected pairs, 309 GF of
    the held experts at the uniform share; the head 2,549 GF."""
    config = M.Cell(CELL).config
    from benchmark import reference
    c = reference.model(config).forward_counts(config)
    assert (c["selected_pairs"], c["causal_pairs"]) == (65_012_736,
                                                        536_887_296)
    for group, gf in (("attn_proj", 4 * 1237), ("moe_experts", 4 * 309),
                      ("head", 2549)):
        assert c[group] / 1e9 == pytest.approx(gf, rel=2e-3), group
    index_fwd = 2 * 16 * 64 * c["causal_pairs"]
    index_bwd = 2 * 2 * 16 * 64 * c["selected_pairs"]
    assert index_fwd / 1e9 == pytest.approx(1100, rel=2e-3)
    # three passes of the indexer's count are the forward over every causal
    # pair and two passes of the projections; its backward over the
    # selected pairs runs in the attention's kernels and is counted there
    assert 3 * c["dsa_index"] == pytest.approx(4 * (index_fwd + 2 * 148e9),
                                               rel=2e-3)
    assert 3 * c["attn_sparse"] == pytest.approx(
        4 * (3 * 1065e9 + index_bwd), rel=2e-3)


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
COUNTS = {"forward_flops": 70.0, "attn_sparse": 20.0, "dsa_index": 10.0}
RED = {"busy_s": 12.0, "steps": 1,
       "by_group": {"attn_sparse": 2.0, "dsa_index": 0.5, "dsa_select": 1.5,
                    "dsa_kl": 1.0, "moe_experts": 2.0}}


def ev(**over):
    e = {"peak": PEAK, "trace": RED, "flop_counts": COUNTS,
         "traced": {"rows": 2, "wall_s": 9.0}, "mode": "train", "chips": 1,
         "counters0": {"steps_total": 10.0, "step_seconds_total": 1.0,
                       "data_wait_seconds_total": 0.1,
                       "dsa_blocks_touched_total": 100.0,
                       "dsa_blocks_causal_total": 200.0},
         "counters1": {"steps_total": 11.0, "step_seconds_total": 2.0,
                       "data_wait_seconds_total": 0.2,
                       "dsa_blocks_touched_total": 190.0,
                       "dsa_blocks_causal_total": 300.0}}
    e.update(over)
    return e


def test_the_manifest_finds_the_cells_metrics():
    cell = M.Cell(CELL)
    names = {m["name"] for m in cell.per_layer()}
    # every accepted metric without a list, and the four of this cell
    assert names >= NEW | {m["name"] for m in cell.manifest["per_layer"]
                           if "workloads" not in m}
    assert "attn_latent_roofline.train" not in names
    for old in ("train_flagship_600", "train_b4_380", "train_phi4flash_long",
                "train_granite4h_long", "train_lfm2moe_8k",
                "train_glm47flash_mla"):
        assert not NEW & {m["name"] for m in M.Cell(old).per_layer()}
    out = M.read_per_layer(cell, ev())
    # three forward passes of 20 at 100 a second over 2 rows, over 2 s
    assert out["attn_sparse_roofline.train"]["value"] == \
        pytest.approx(100.0 * 3 * 20 * 2 / 100.0 / 2.0)
    # the indexer's 10 over the projections' and the selection's 2 s
    assert out["dsa_index_roofline.train"]["value"] == \
        pytest.approx(100.0 * 3 * 10 * 2 / 100.0 / 2.0)
    assert out["dsa_share.train"]["value"] == pytest.approx(100 * 5 / 12.0)
    assert out["dsa_block_fill.train"]["value"] == pytest.approx(90.0)
    # a program without the scopes and the counters (the parent): left out,
    # never 0
    bare = M.read_per_layer(cell, ev(
        trace=dict(RED, by_group={"moe_experts": 2.0}),
        counters0={k: v for k, v in ev()["counters0"].items()
                   if not k.startswith("dsa_")},
        counters1={k: v for k, v in ev()["counters1"].items()
                   if not k.startswith("dsa_")}))
    assert not NEW & set(bare)


def test_the_entries_this_pr_appended_keep_the_manifests_form():
    """A manifest line over 200 characters is refused before any run."""
    cell = M.Cell(CELL)
    root = os.path.join(os.path.dirname(__file__), "..", "..")
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell.entry["config"])
    for line in (config["why"], config["source"], cell.entry["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable() and line.isascii()
    # appended after the entries that were there (later PRs append after
    # these): the accepted GLM-4.7-Flash entries come first
    names = [c["name"] for c in manifest["configs"]]
    assert names.index(config["name"]) > names.index("glm47_flash_5l")
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("train_glm47flash_mla")
    metrics = [m["name"] for m in manifest["per_layer"]]
    at = metrics.index("attn_sparse_roofline.train")
    assert at > metrics.index("mla_share.train")
    assert metrics[at:at + 4] == [
        "attn_sparse_roofline.train", "dsa_index_roofline.train",
        "dsa_share.train", "dsa_block_fill.train"]
    for m in manifest["per_layer"][at:at + 4]:
        assert m["workloads"] == [CELL]
