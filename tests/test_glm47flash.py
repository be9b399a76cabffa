"""models/glm4moelite.py (multi-head latent attention, a leading dense
layer, a shared expert beside routed experts held in part) against the plain
reference ``benchmark/reference/glm47flash.py`` on seeded weights, at tiny
sizes on the CPU: each kind of layer, latent attention against a per-head
attention written from the equations, the eight shares of an expert layer
against the uncut layer, the whole model, the published constructor's size
by shape, the planted faults, three AdamW steps through ``make_train_step``
with accumulated microbatches, and one tiny run through ``runners/train.py``.

Tolerances: everything here is float32 on the CPU, where the program and the
reference differ by the order of their sums alone (a sorted, grouped product
against every expert on every token; flash attention's running softmax over
one concatenated key against a whole softmax over two score terms).  A
layer's outputs and gradients agree to 2e-4 of their norm, the model's
logits to 2e-5 absolute, its gradients to 1e-3, three steps' parameter
changes to 2e-2 (Adam divides by the gradient's own magnitude).  The seed is
one on which no selection sits on a rounding edge: a selection that flips is
a step, not a rounding.
"""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import faults_glm47flash as FAULTS        # noqa: E402
from benchmark.lib import weights as W                        # noqa: E402
from benchmark.reference import glm47flash as R               # noqa: E402
from benchmark.reference import optim_adamw as O              # noqa: E402
from deepfake_detection_tpu.models import create_model        # noqa: E402
from deepfake_detection_tpu.models import glm4moelite as G    # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "glm47_flash_5l.json")) as _f:
    CELL = json.load(_f)
with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                       "tiny_glm47flash_f32.json")) as _f:
    TINY = json.load(_f)
SPEC = R.model_spec(TINY)
LAYER_TOL = 2e-4
# the tiny layer's widths, as models/glm4moelite.py:_TINY has them
WIDTHS = dict(d_model=64, n_heads=4, q_lora_rank=24, kv_lora_rank=16,
              qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
              d_ff=96, d_expert=32, n_shared_experts=1,
              routed_scaling_factor=1.8, rope_theta=1e6, eps=1e-5)


@pytest.fixture(scope="module")
def variables():
    return W.make_variables(7, *R.param_shapes(SPEC), leaf=R.init_leaf)


def _ids(rows=2, l=40, seed=1, vocab=512):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, l), 0, vocab)
    return ids, jnp.concatenate(
        [ids[:, 1:], -jnp.ones((rows, 1), jnp.int32)], 1)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


def _worst(g1, g2):
    return max(_rel(a, b) for a, b in zip(jax.tree.leaves(g1),
                                          jax.tree.leaves(g2)))


# ---- the sizes, by shapes alone ---------------------------------------------

def _count(name):
    m = create_model(name)
    s = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32)))
    return m, s, sum(x.size for x in jax.tree.leaves(s["params"]))


MLA = 2048 * 768 + 768 + 768 * 20 * 256 + 2048 * 576 + 512 \
    + 512 * 20 * 448 + 20 * 256 * 2048
EXPERT = 3 * 2048 * 1536


def test_published_model_follows_the_published_config_and_is_29_94b():
    """Without the multi-token-prediction layer, which is not built."""
    m, s, n = _count("glm47_flash")
    assert MLA == 21_759_232
    assert (m.n_layers, m.first_k_dense, m.vocab_rows, m.held) == \
        (47, 1, 154880, (0, 64))
    dense = MLA + 2 * 2048 + 3 * 2048 * 10240
    moe = MLA + 2 * 2048 + EXPERT + 2048 * 64 + 64 * EXPERT
    assert n == dense + 46 * moe + 2 * 154880 * 2048 + 2048 \
        == 29_943_390_976
    assert f"{n:,}" in CELL["source_detail"]
    # the selection bias is a buffer, not a parameter: 46 layers of 64
    assert sum(x.size for x in jax.tree.leaves(s["batch_stats"])) == 46 * 64


def test_the_cut_is_five_published_layers_at_published_widths_and_591m():
    m, s, n = _count("glm47_flash_5l")
    whole = create_model("glm47_flash")
    assert (m.n_layers, m.first_k_dense, m.vocab_rows, m.held) == \
        (5, 1, 19360, (0, 8))
    assert n == 591_294_720 == 84_677_888 + 4 * 106_829_056 \
        + 79_298_560 + 2048
    assert f"{n:,}" in CELL["deployment"]
    assert "lm_head" in s["params"] and "embed" in s["params"]
    for field, key in (("d_model", "hidden_size"),
                       ("d_ff", "intermediate_size"),
                       ("d_expert", "moe_intermediate_size"),
                       ("n_heads", "num_attention_heads"),
                       ("q_lora_rank", "q_lora_rank"),
                       ("kv_lora_rank", "kv_lora_rank"),
                       ("qk_nope_head_dim", "qk_nope_head_dim"),
                       ("qk_rope_head_dim", "qk_rope_head_dim"),
                       ("v_head_dim", "v_head_dim"),
                       ("n_shared_experts", "n_shared_experts"),
                       ("top_k", "num_experts_per_tok"),
                       ("eps", "rms_norm_eps"), ("rope_theta", "rope_theta"),
                       ("first_k_dense", "first_k_dense_replace"),
                       ("routed_scaling_factor", "routed_scaling_factor")):
        assert getattr(m, field) == getattr(whole, field) == CELL[key], field
    assert m.n_experts == whole.n_experts == CELL["num_experts_published"]
    assert m.held == (CELL["held_first"], CELL["n_routed_experts"])
    spec = R.model_spec(CELL)
    assert (spec["held"], spec["experts"], spec["layers"], spec["rows"]) == \
        (m.held, 64, 5, 19360)
    # the reference's tree is the program's, leaf for leaf
    prog = {tuple(k.key for k in path): tuple(x.shape) for path, x in
            jax.tree_util.tree_flatten_with_path(s["params"])[0]}
    assert prog == dict(W._flatten(R.param_shapes(spec)[0]))


def test_the_tiny_model_is_the_cuts_schedule():
    tiny = create_model("glm47_flash_tiny")
    assert (tiny.n_layers, tiny.first_k_dense) == \
        (SPEC["layers"], SPEC["dense"]) == (5, 1)
    assert (tiny.held, tiny.n_experts, tiny.top_k) == \
        (SPEC["held"], SPEC["experts"], SPEC["top_k"]) == ((0, 2), 8, 2)
    assert tiny.mla_layers() == 5 and tiny.expert_layers == 4


# ---- each kind of layer: forward and gradient against the reference --------

def _layer(dense, **kw):
    kw = dict(dict(n_experts=8, top_k=2, held=(0, 2)), **kw)
    return G._Layer(dense=dense, **WIDTHS, **kw)


def _layer_pair(variables, index, quant=None, **kw):
    """The program's and the reference's scalar function of one layer."""
    dense = index < SPEC["dense"]
    name = f"layers_{index}"
    p = variables["params"][name]
    stats = {} if dense else variables["batch_stats"][name]
    ks = jax.random.split(jax.random.PRNGKey(index), 2)
    x, w = (jax.random.normal(k, (40, 64)) for k in ks)
    mod = _layer(dense, **kw)
    prog = lambda p, x: jnp.sum(mod.apply(                    # noqa: E731
        {"params": p, "batch_stats": stats}, x[None], False)[0] * w)
    ref = lambda p, x: jnp.sum(R.layer_forward(               # noqa: E731
        p, stats.get("expert_bias"), x, SPEC, dense, quant) * w)
    return prog, ref, p, x


@pytest.mark.parametrize("index,kw", [
    (0, {"attn_impl": "full"}), (0, {"attn_impl": "flash"}),
    (1, {"attn_impl": "full", "moe_impl": "xla"}),
    (2, {"attn_impl": "flash", "moe_impl": "pallas"})],
    ids=["dense-full", "dense-flash", "experts-full-xla",
         "experts-flash-pallas"])
def test_each_layer_forward_and_gradient_match_the_reference(variables,
                                                             index, kw):
    prog, ref, p, x = _layer_pair(variables, index, **kw)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(prog(p, x), ref(p, x), rtol=1e-4)
        assert _worst(jax.grad(prog, (0, 1))(p, x),
                      jax.grad(ref, (0, 1))(p, x)) < LAYER_TOL


@pytest.mark.parametrize("index", [0, 1], ids=["dense", "experts"])
def test_one_precision_lower_fails_the_layer_tolerance(variables, index):
    _, ref, p, x = _layer_pair(variables, index)
    _, low, _, _ = _layer_pair(variables, index, quant="bf16")
    with jax.default_matmul_precision("highest"):
        assert _worst(jax.grad(low, (0, 1))(p, x),
                      jax.grad(ref, (0, 1))(p, x)) > 5 * LAYER_TOL


def _rms(x, scale, eps=1e-5):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotate(x, theta=1e6):
    """(L, heads, dh) rotate-half: channel i with i + dh / 2."""
    l, dh = x.shape[0], x.shape[-1]
    ang = np.arange(l)[:, None] * theta ** (-2.0 * np.arange(dh // 2)
                                            / dh)[None, :]
    c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return np.concatenate([a * c - b * s, b * c + a * s], -1)


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_latent_attention_is_the_per_head_attention_of_the_equations(
        variables, attn):
    """The dense layer with its MLP's down projection zeroed is ``x +
    MLA(RMSNorm(x))``; MLA written here in float64, head by head, with the
    score as its two terms: a head's own 12 channels and the 4 rotated
    channels of the one shared key, over sqrt(16)."""
    p = jax.tree.map(np.asarray, variables["params"]["layers_0"])
    p["down"] = {"kernel": np.zeros_like(p["down"]["kernel"])}
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (24, 64)),
                   np.float64)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(_layer(True, attn_impl=attn).apply(
            {"params": p}, jnp.asarray(x, jnp.float32)[None])[0]) - x
    k = {n: np.asarray(v["kernel"] if isinstance(v, dict) and "kernel" in v
                       else v["scale"], np.float64)
         for n, v in p.items() if isinstance(v, dict)}
    y = _rms(x, k["input_layernorm"])
    q = (_rms(y @ k["q_a_proj"], k["q_a_norm"]) @ k["q_b_proj"]).reshape(
        24, 4, 16)
    lat = y @ k["kv_a_proj"]
    kv = (_rms(lat[:, :16], k["kv_a_norm"]) @ k["kv_b_proj"]).reshape(
        24, 4, 28)
    q_n, q_r = q[..., :12], _rotate(q[..., 12:])
    k_n, v = kv[..., :12], kv[..., 12:]
    k_r = _rotate(lat[:, None, 16:])[:, 0]
    heads = []
    for h in range(4):
        s = (q_n[:, h] @ k_n[:, h].T + q_r[:, h] @ k_r.T) / 4.0
        s = np.where(np.tril(np.ones((24, 24), bool)), s, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        heads.append((e / e.sum(-1, keepdims=True)) @ v[:, h])
    want = np.concatenate(heads, -1) @ k["o_proj"]
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """A tiny layer of 64 experts, top-4: the program's layer holding
    experts (8 i, 8) for i = 0..7, with everything every chip computes alike
    (attention, the shared expert, the residual) counted once, adds up to
    the uncut reference layer: sum_i y_i - 7 base, where base is the layer
    whose routed experts give nothing."""
    spec = dict(SPEC, experts=64, held=(0, 64), top_k=4)
    shapes = R._layer_shapes(False, spec)
    full = W.make_variables(11, {"layers_1": shapes},
                            {"layers_1": {"expert_bias": (64,)}},
                            leaf=R.init_leaf)
    p, bias = full["params"]["layers_1"], \
        full["batch_stats"]["layers_1"]["expert_bias"]
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 64))
    stats = {"expert_bias": bias}

    def share(first, zero=False):
        q = dict(p, experts_w13=p["experts_w13"][first:first + 8],
                 experts_w2=p["experts_w2"][first:first + 8] * (not zero))
        return _layer(False, n_experts=64, top_k=4, held=(first, 8),
                      attn_impl="full", moe_impl="xla").apply(
            {"params": q, "batch_stats": stats}, x[None])[0]

    with jax.default_matmul_precision("highest"):
        parts = sum(share(8 * i) for i in range(8)) - 7 * share(0, True)
        want = R.layer_forward(p, bias, x, spec, False)
        # every token's four selections fall on the shares: none is lost
        sel = R.selected(p, bias, R.ffn_input(p, x, spec)[1], spec)[1]
    assert sel.shape == (40, 4)
    assert _rel(parts, want) < 2e-5
    # and one share alone is not the layer
    assert _rel(share(0), want) > 1e-2


@pytest.mark.parametrize("attn,moe,remat", [
    ("full", "xla", "none"), ("flash", "pallas", "full")])
def test_model_logits_loss_and_gradients_match_the_reference(variables, attn,
                                                             moe, remat):
    ids, tg = _ids()
    params, stats = variables["params"], variables["batch_stats"]
    m = create_model("glm47_flash_tiny", attn_impl=attn, moe_impl=moe,
                     remat_policy=remat)
    with jax.default_matmul_precision("highest"):
        logits = m.apply(variables, ids)
        (loss, _), g = jax.value_and_grad(
            lambda p: m.apply({"params": p, "batch_stats": stats}, ids, tg,
                              method="sequence_loss"), has_aux=True)(params)
    ref = R.inference_forward(params, stats, ids, SPEC)
    assert logits.shape == (2, 40, 512) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, ref, atol=2e-5)
    # the reference finds the configuration's own buffers where it is
    # handed none (drivers/train_seq.py hands it none)
    rl, rg, _, _ = R.loss_and_grads(params, {}, ids, tg, SPEC)
    assert abs(float(loss) - float(rl)) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree.leaves(rg)):
        assert _rel(a, b) < 1e-3, (jax.tree_util.keystr(path), _rel(a, b))


@pytest.mark.parametrize("fault", FAULTS.MODEL_FAULTS)
def test_each_planted_fault_moves_the_compared_numbers(variables, fault):
    """The loss or a gradient leaf of the faulty program leaves the
    reference by far more than the sound program's 1e-3."""
    ids, tg = _ids()
    params, stats = variables["params"], variables["batch_stats"]
    m = FAULTS.faulty_model(create_model("glm47_flash_tiny",
                                         attn_impl="full", moe_impl="xla"),
                            fault)
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(
            lambda p: m.apply({"params": p, "batch_stats": stats}, ids, tg,
                              method="sequence_loss"), has_aux=True)(params)
    rl, rg, _, _ = R.loss_and_grads(params, {}, ids, tg, SPEC)
    assert _worst(g, rg) > 0.02, fault
    # the model file is as it was once the faulty trace is done
    assert G.mla_scale(192, 64) == 1 / 16
    assert FAULTS.faulty_model(m, None) is m


def test_named_scopes_survive_into_the_lowered_program(variables):
    m = create_model("glm47_flash_tiny", attn_impl="full")
    ids, tg = _ids(1, 24)
    text = jax.jit(jax.grad(lambda p: m.apply(
        {"params": p, "batch_stats": variables["batch_stats"]}, ids, tg,
        method="sequence_loss")[0])).lower(
            variables["params"]).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("/embed/", "layers_0/.*mla_proj", "layers_0/.*attn_latent",
                  "layers_0/.*mlp_dense", "layers_1/.*moe_shared",
                  "layers_1/.*moe_router", "layers_2/.*moe_dispatch",
                  "layers_3/.*moe_experts", "layers_4/.*moe_combine",
                  "lm_head_loss"):
        assert any(re.search(scope, n) for n in names), scope
    # the cell's trace_groups file every scope under its own name
    groups = CELL["trace_groups"]
    first = lambda n: next((g for g, pat in groups            # noqa: E731
                            if re.search(pat, n)), None)
    found = {first(n) for n in names}
    assert {"mla_proj", "attn_latent", "mlp_dense", "moe_shared",
            "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
            "lm_head_loss", "embed"} <= found
    assert [g for g, _ in groups][-2:] == ["layers_other", "optimizer"]


# ---- three optimizer steps through the one train step ----------------------

@pytest.mark.parametrize("grad_accum", [1, 2], ids=["whole", "accumulated"])
def test_three_adamw_steps_match_the_reference(variables, grad_accum):
    from deepfake_detection_tpu.config import TrainConfig
    from deepfake_detection_tpu.optim import create_optimizer
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_train_step)
    cfg = TrainConfig.from_args(
        ["--model", "glm47_flash_tiny", "--model-version", "",
         "--dataset", "synthetic-tokens", "--seq-len", "40", "-b", "2",
         "--grad-accum", "2", "--opt", "adamw", "--opt-beta2", "0.95",
         "--lr", "1e-3", "--weight-decay", "1e-4", "--clip-grad", "1.0",
         "--compute-dtype", "float32", "--attn-impl", "full"])
    model = create_model("glm47_flash_tiny", attn_impl="full",
                         remat_policy="full")
    tx = create_optimizer(cfg, learning_rate=cfg.lr)
    p0 = jax.tree.map(np.asarray, variables["params"])
    stats0 = jax.tree.map(np.asarray, variables["batch_stats"])
    state = create_train_state(
        jax.tree.map(jnp.asarray, {"params": p0, "batch_stats": stats0}), tx)
    step = make_train_step(model, tx, clip_grad=cfg.clip_grad,
                           grad_accum=grad_accum)
    kw = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
              clip=1.0)
    rp = jax.tree.map(jnp.asarray, p0)
    ropt = O.init(rp)
    rng = jax.random.PRNGKey(0)
    for i in range(3):
        ids, tg = _ids(rows=4, seed=10 + i)
        with jax.default_matmul_precision("highest"):
            state, metrics = step(state, ids, tg, rng)
        loss, grads, _, _ = R.loss_and_grads(rp, stats0, ids, tg, SPEC)
        rp, ropt, g = O.update(rp, grads, ropt, **kw)
        assert abs(float(metrics["loss"]) - float(loss)) < 2e-5 * (i + 1)
        # four rows of 40 tokens through four expert layers
        counts = np.asarray(metrics["moe_counts"])
        assert counts[0] == 4 * 40 * 4 and 0 < counts[1] < 2 * counts[0]
        if i == 0:
            g1 = O.program_first_gradient(state.opt_state, **kw)
            for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g)):
                assert _rel(jnp.asarray(a), b) < 1e-3
    for (path, a), b, z in zip(
            jax.tree_util.tree_flatten_with_path(state.params)[0],
            jax.tree.leaves(rp), jax.tree.leaves(p0)):
        assert _rel(a - z, b - z) < 2e-2, jax.tree_util.keystr(path)


# ---- the normal runner ------------------------------------------------------

def test_runner_trains_and_logs_the_latent_attention_census(tmp_path):
    from deepfake_detection_tpu.runners.train import launch_main
    out = launch_main([
        "--model", "glm47_flash_tiny", "--model-version", "",
        "--dataset", "synthetic-tokens", "--seq-len", "32", "-b", "1",
        "--grad-accum", "2", "--opt", "adamw", "--lr", "1e-3",
        "--weight-decay", "1e-4", "--sched", "step", "--decay-rate", "1.0",
        "--epochs", "1", "--clip-grad", "1.0", "--checkpoint-policy", "full",
        "--attn-impl", "full", "--compute-dtype", "float32", "--workers", "1",
        "--log-interval", "4", "--recovery-interval", "0",
        "--output", str(tmp_path)])
    assert np.isfinite(out["loss"])
    run = tmp_path / os.listdir(tmp_path)[0]
    events = [json.loads(line) for line in open(run / "telemetry.jsonl")]
    start = next(e for e in events if e.get("event") == "run_start")
    assert start["mla_layers"] == 5
    assert (start["moe_kernel_layers"], start["moe_xla_layers"]) == (0, 4)
    last = [e for e in events if "counters" in e][-1]["counters"]
    assert last["moe_routed_tokens_total"] > 0
