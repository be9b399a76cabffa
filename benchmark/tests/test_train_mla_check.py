"""``drivers/train_seq.py`` on a configuration with latent attention
and a shared expert beside routed experts held in part, at a size a test run
can hold (the five-layer GLM-4.7-Flash cut at d 64, 8 experts top-2 of which
two are held, 48-token rows, batch 2 x grad-accum 2, on the CPU, float32),
the counts of ``lib/flops_mla.py`` against a hand count, the cell's entries
and the readings behind its limits.

* a sound run of ``drivers/train_seq.run`` (without its look for a chip)
  comes out correct, feeds whole documents and builds nothing in its window;
* the control -- the reference put in the program's place one precision
  lower (bfloat16 under this float32 configuration) -- comes out not correct;
* each planted fault comes out not correct: a state left unchanged, half of
  the rows' targets left out, the softmax scale over the key's own 192
  channels, the latent's norm dropped, a rotary key per head, the shared
  expert dropped.
"""
import json
import os
import time

import pytest

from benchmark.drivers import train_seq as D
from benchmark.drivers.train_tokens import shift
from benchmark.lib import faults_glm47flash as FAULTS
from benchmark.lib import flops_mla as F
from benchmark.lib import manifest as M

MAN = M.load_json(os.path.join(M.BENCH, "tests", "tiny",
                               "BENCHMARK.tiny_mla.json"))
SEED = 3000000019                       # past 2**31, as a run's may be
CELL = "train_glm47flash_mla"
NEW = {"attn_latent_roofline.train", "mla_share.train"}


def _cell():
    return M.Cell("train_tiny_mla", MAN)


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
    assert b * a == train["batch"] == 4 and a == train["grad_accum"]
    assert train["batch"] * train["seq_len"] == 32768
    assert int(flags[flags.index("--seq-len") + 1]) == train["seq_len"]


# the published config.json's numbers (huggingface.co/zai-org/GLM-4.7-Flash)
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


def test_the_configuration_keeps_every_published_number():
    """Every number of the published config is in the file under the same
    key, but the four under ``reduced``."""
    config = M.Cell(CELL).config
    assert config["source"] == ("https://huggingface.co/zai-org/"
                                "GLM-4.7-Flash/blob/main/config.json")
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "num_nextn_predict_layers"]
    assert sorted(config["reduced_detail"]) == sorted(config["reduced"])
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["num_nextn_predict_layers"]) == (5, 1, 0)
    assert config["n_routed_experts"] * 8 == config["num_experts_published"] \
        == PUBLISHED["n_routed_experts"]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "num_experts_per_tok", "n_shared_experts"):
        assert key not in config["reduced"]


def test_committed_limits_separate_the_recorded_readings():
    """``readings/glm47_flash_5l.jsonl``: what the chip read at the cell's
    own size (``calibrate_seq.py`` and the cell's runs; PERF.md section 6
    gives the ranges).  Under the committed limits every sound run is
    correct and the float8 control and every planted fault are not."""
    limits = M.Cell(CELL).config["reference"]["limits"]
    path = os.path.join(M.BENCH, "tests", "readings", "glm47_flash_5l.jsonl")
    seeds = {}
    for r in (json.loads(line) for line in open(path) if line.strip()):
        # a control is read through ``compare`` alone: no batch numbers
        ok, compared = D.judge(r["numbers"], limits,
                               every_limit=r["kind"] == "program")
        assert ok is (r["kind"] == "program"), (r["kind"], r["seed"],
                                                compared)
        seeds.setdefault(r["kind"], set()).add(r["seed"])
    assert len(seeds["program"]) >= 3, seeds
    for kind in ("control",) + D.STEP_FAULTS + FAULTS.MODEL_FAULTS:
        assert seeds.get(kind), (kind, seeds)
    text = M.Cell(CELL).config["reference"]["readings"]
    for name in set(limits) - {"batch_gap", "target_gap"}:
        assert name in text


# ---------------------------------------------------------------------------
# operations from shapes
# ---------------------------------------------------------------------------

def test_counts_agree_with_a_hand_count_at_a_tiny_size():
    """d 8, 2 heads whose keys are 3 + 1 wide and values 2, q rank 4, kv
    rank 3, dense width 12, experts of 6 and a shared one, top-2 of 8 with
    2 held, 10 rows; one row of 5 positions through a dense layer and an
    expert layer."""
    spec = {"d": 8, "heads": 2, "q_rank": 4, "kv_rank": 3, "nope": 3,
            "rope": 1, "dv": 2, "ff": 12, "f": 6, "shared": 6, "experts": 8,
            "held": (2, 2), "top_k": 2, "rows": 10}
    c = F.counts_for(spec, (True, False), 5)
    proj = 2 * 5 * (8 * 4 + 4 * 2 * 4 + 8 * 4 + 3 * 2 * 5 + 2 * 2 * 8)
    assert c["mla_proj"] == 2 * proj
    # a causal pair: 2 x 4 for the two score terms, 2 x 2 for the values
    assert c["attn_latent"] == 2 * 2 * (2 * 4 + 2 * 2) * 15
    assert c["mlp_dense"] == 2 * 5 * 8 * 24 + 2 * 5 * 12 * 8
    assert c["moe_shared"] == 2 * 5 * 8 * 12 + 2 * 5 * 6 * 8
    assert c["moe_router"] == 2 * 5 * 8 * 8
    assert c["moe_experts"] == 5 * 0.5 * (2 * 3 * 8 * 6)
    assert (c["moe_tokens"], c["moe_weight_elems"]) == (5, 2 * 3 * 8 * 6)
    assert c["head"] == 2 * 5 * 8 * 10
    assert c["forward_flops"] == sum(c[k] for k in F.GROUPS)


def test_the_cells_counts_are_the_issues_figures():
    """A row of 8,192 tokens is 1,783 GF of latent attention's
    projections and 3,436 GF of its pairs over the five layers, 1,031 GF
    of the dense MLP, 619 of the shared experts, 309 of the held experts at
    the uniform share and 650 of the head: about 7.8 TF, latent attention
    two thirds of it."""
    config = M.Cell(CELL).config
    from benchmark import reference
    c = reference.model(config).forward_counts(config)
    for group, gf in (("mla_proj", 1783), ("attn_latent", 3436),
                      ("mlp_dense", 1031), ("moe_shared", 619),
                      ("moe_experts", 309), ("head", 650)):
        assert c[group] / 1e9 == pytest.approx(gf, rel=2e-3), group
    share = (c["mla_proj"] + c["attn_latent"]) / c["forward_flops"]
    assert share == pytest.approx(0.666, abs=0.01)


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
COUNTS = {"forward_flops": 70.0, "mla_proj": 10.0, "attn_latent": 20.0}
RED = {"busy_s": 12.0, "steps": 1,
       "by_group": {"mla_proj": 1.0, "attn_latent": 2.0, "mlp_dense": 2.0}}


def ev(**over):
    e = {"peak": PEAK, "trace": RED, "flop_counts": COUNTS,
         "traced": {"rows": 2, "wall_s": 9.0}, "mode": "train", "chips": 1,
         "counters0": {"steps_total": 10.0, "step_seconds_total": 1.0,
                       "data_wait_seconds_total": 0.1},
         "counters1": {"steps_total": 11.0, "step_seconds_total": 2.0,
                       "data_wait_seconds_total": 0.2}}
    e.update(over)
    return e


def test_the_manifest_finds_the_cells_metrics():
    cell = M.Cell(CELL)
    names = {m["name"] for m in cell.per_layer()}
    # every accepted metric without a list, and the two of this cell
    assert names >= NEW | {m["name"] for m in cell.manifest["per_layer"]
                           if "workloads" not in m}
    assert not {"attn_rope_roofline.train", "moe_share.train"} & names
    for old in ("train_flagship_600", "train_b4_380", "train_phi4flash_long",
                "train_granite4h_long", "train_lfm2moe_8k"):
        assert not NEW & {m["name"] for m in M.Cell(old).per_layer()}
    out = M.read_per_layer(cell, ev())
    # three forward passes of 30 at 100 a second over 2 rows, over 3 s
    assert out["attn_latent_roofline.train"]["value"] == \
        pytest.approx(100.0 * 3 * 30 * 2 / 100.0 / 3.0)
    assert out["mla_share.train"]["value"] == pytest.approx(100 * 3 / 12.0)
    # a program without the scopes (the parent): left out, never 0
    bare = M.read_per_layer(cell, ev(trace=dict(RED, by_group={
        "mlp_dense": 2.0})))
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
    assert manifest["configs"][-1] is config
    assert manifest["workloads"][-1] == cell.entry
    assert [m["name"] for m in manifest["per_layer"][-2:]] == \
        ["attn_latent_roofline.train", "mla_share.train"]
