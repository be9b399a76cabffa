"""models/lfm2moe.py (gated short convolutions, RoPE / QK-norm grouped
attention, a sigmoid router with a selection bias over experts held in part)
against the plain reference ``benchmark/reference/lfm2moe.py`` on seeded
weights, at tiny sizes on the CPU: every kind of layer, the whole model, the
rotation against a hand-written complex one, the published constructor's
size by shape, three AdamW steps through ``make_train_step`` with
accumulated microbatches, the routing counters from the device to the
telemetry, and one tiny run through ``runners/train.py`` with a restore.

Tolerances: everything here is float32 on the CPU, where the program and the
reference differ by the order of their sums alone (a sorted, grouped product
against every expert on every token; flash attention's running softmax
against a whole one).  A layer's outputs and gradients agree to 2e-4 of
their norm, the model's logits to 2e-5 absolute, its gradients to 1e-3,
three steps' parameter changes to 2e-2 (Adam divides by the gradient's own
magnitude).  The seed is one on which no selection sits on a rounding edge:
a selection that flips is a step, not a rounding.
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

from benchmark.lib import weights as W                        # noqa: E402
from benchmark.reference import lfm2moe as R                  # noqa: E402
from benchmark.reference import optim_adamw as O              # noqa: E402
from deepfake_detection_tpu.models import create_model        # noqa: E402
from deepfake_detection_tpu.models import lfm2moe as L        # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "lfm2_24b_a2b_5l.json")) as _f:
    CELL = json.load(_f)
with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                       "tiny_lfm2moe_f32.json")) as _f:
    TINY = json.load(_f)
SPEC = R.model_spec(TINY)
LAYERS = R.schedule(SPEC)
LAYER_TOL = 2e-4


@pytest.fixture(scope="module")
def variables():
    return W.make_variables(7, *R.param_shapes(SPEC), leaf=R.init_leaf)


def _ids(rows=2, l=40, seed=1, vocab=512):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, l), 0, vocab)
    return ids, jnp.concatenate(
        [ids[:, 1:], -jnp.ones((rows, 1), jnp.int32)], 1)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


# ---- the schedule and the sizes, by shapes alone ---------------------------

def _count(name):
    m = create_model(name)
    s = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32)))
    return m, s, sum(x.size for x in jax.tree.leaves(s["params"]))


def test_published_model_follows_the_published_config_and_is_23_84b():
    m, s, n = _count("lfm2_24b_a2b")
    assert list(m.layer_types) == CELL["layer_types"]
    assert [i for i, k in enumerate(m.layer_types) if k == L.ATTENTION] == \
        list(range(2, 40, 4))
    assert (m.num_dense_layers, m.vocab_rows, m.held) == (2, 65536, (0, 64))
    # two dense conv layers, ten attention and 28 conv layers of 64 experts
    expert = 3 * 2048 * 1536
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048 + 2 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64 + 2 * 2048
    assert n == 2 * (conv + 3 * 2048 * 11776) \
        + 10 * (attn + 2048 * 64 + 64 * expert) \
        + 28 * (conv + 2048 * 64 + 64 * expert) \
        + 65536 * 2048 + 2048 == 23_843_659_008
    # the selection bias is a buffer, not a parameter: 38 layers of 64
    assert sum(x.size for x in jax.tree.leaves(s["batch_stats"])) == 38 * 64
    assert "moe_counts" not in s


def test_the_cut_is_five_published_layers_at_published_widths_and_469m():
    m, _, n = _count("lfm2_24b_a2b_5l")
    whole = create_model("lfm2_24b_a2b")
    assert CELL["layers_kept"] == [0, 2, 3, 4, 5]
    assert m.layer_types == tuple(whole.layer_types[i]
                                  for i in CELL["layers_kept"]) == \
        (L.CONV, L.ATTENTION, L.CONV, L.CONV, L.CONV)
    assert (m.num_dense_layers, m.vocab_rows, m.held) == (1, 8192, (0, 8))
    assert n == 469_284_992 == 89_139_200 + 86_118_528 + 3 * 92_416_000 \
        + 16_777_216 + 2_048
    assert f"{n:,}" in CELL["source_detail"] + CELL["deployment"]
    for field, key in (("d_model", "hidden_size"),
                       ("d_ff", "intermediate_size"),
                       ("d_expert", "moe_intermediate_size"),
                       ("n_heads", "num_attention_heads"),
                       ("n_kv_heads", "num_key_value_heads"),
                       ("top_k", "num_experts_per_tok"),
                       ("d_conv", "conv_L_cache"), ("eps", "norm_eps"),
                       ("routed_scaling_factor", "routed_scaling_factor")):
        assert getattr(m, field) == getattr(whole, field) == CELL[key], field
    assert m.n_experts == whole.n_experts == CELL["num_experts_published"]
    assert m.held == (CELL["held_first"], CELL["num_experts"])
    assert m.rope_theta == CELL["rope_parameters"]["rope_theta"] == 1e6
    assert m.head_dim * m.n_heads == m.d_model
    spec = R.model_spec(CELL)
    assert (spec["held"], spec["experts"], spec["kinds"]) == \
        (m.held, 64, m.layer_types)


def test_the_tiny_model_is_the_cuts_schedule():
    tiny = create_model("lfm2_24b_a2b_tiny")
    cut = create_model("lfm2_24b_a2b_5l")
    assert tiny.layer_types == cut.layer_types == SPEC["kinds"]
    assert tiny.num_dense_layers == cut.num_dense_layers == SPEC["dense"]
    assert (tiny.held, tiny.n_experts, tiny.top_k) == \
        (SPEC["held"], SPEC["experts"], SPEC["top_k"]) == ((0, 2), 8, 2)


# ---- the rotation -----------------------------------------------------------

def test_rope_is_a_complex_rotation_of_the_half_pairs():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 16))
    got = np.asarray(L.rope(x, 1e6), np.float64)
    xs = np.asarray(x, np.float64)
    z = xs[..., :8] + 1j * xs[..., 8:]                # channel i with i + 8
    turn = np.exp(1j * np.arange(24)[:, None]
                  * 1e6 ** (-2.0 * np.arange(8) / 16)[None, :])
    want = z * turn[None, :, None, :]
    np.testing.assert_allclose(got[..., :8], want.real, atol=2e-6)
    np.testing.assert_allclose(got[..., 8:], want.imag, atol=2e-6)
    # position 0 stands still, and a rotation keeps every pair's length
    np.testing.assert_array_equal(got[:, 0], xs[:, 0].astype(np.float32))
    np.testing.assert_allclose(got[..., :8] ** 2 + got[..., 8:] ** 2,
                               np.abs(z) ** 2, rtol=1e-5)
    # the reference's own rotation, written apart, is the same one
    np.testing.assert_allclose(np.asarray(R.rotate(x[0], 1e6)), got[0],
                               atol=2e-6)
    assert L.rope(x.astype(jnp.bfloat16), 1e6).dtype == jnp.bfloat16


# ---- each kind of layer: forward and gradient against the reference --------

def _layer(kind, dense, **kw):
    return L._Layer(
        kind=kind, dense=dense, d_model=64, n_heads=4, n_kv_heads=1,
        head_dim=16, d_ff=96, d_expert=32, n_experts=8, top_k=2, held=(0, 2),
        routed_scaling_factor=1.0, d_conv=3, rope_theta=1e6, eps=1e-5, **kw)


def _layer_pair(variables, index, quant=None, **kw):
    """The program's and the reference's scalar function of one layer."""
    kind, dense = LAYERS[index]
    name = f"layers_{index}"
    p = variables["params"][name]
    stats = {} if dense else variables["batch_stats"][name]
    ks = jax.random.split(jax.random.PRNGKey(index), 2)
    x, w = (jax.random.normal(k, (40, 64)) for k in ks)
    mod = _layer(kind, dense, **kw)
    prog = lambda p, x: jnp.sum(mod.apply(                    # noqa: E731
        {"params": p, "batch_stats": stats}, x[None], False)[0] * w)
    ref = lambda p, x: jnp.sum(R.layer_forward(               # noqa: E731
        p, stats.get("expert_bias"), x, SPEC, kind, dense, quant) * w)
    return prog, ref, p, x


def _worst(g1, g2):
    return max(_rel(a, b) for a, b in zip(jax.tree.leaves(g1),
                                          jax.tree.leaves(g2)))


@pytest.mark.parametrize("index,kw", [
    (0, {}), (1, {"attn_impl": "full", "moe_impl": "xla"}),
    (1, {"attn_impl": "flash", "moe_impl": "pallas"}),
    (2, {"moe_impl": "xla"}), (2, {"moe_impl": "pallas"})],
    ids=["conv-dense", "attention-full-experts-xla",
         "attention-flash-experts-pallas", "conv-experts-xla",
         "conv-experts-pallas"])
def test_each_layer_forward_and_gradient_match_the_reference(variables,
                                                             index, kw):
    prog, ref, p, x = _layer_pair(variables, index, **kw)
    np.testing.assert_allclose(prog(p, x), ref(p, x), rtol=1e-4)
    assert _worst(jax.grad(prog, (0, 1))(p, x),
                  jax.grad(ref, (0, 1))(p, x)) < LAYER_TOL


@pytest.mark.parametrize("index", [0, 1, 2],
                         ids=["conv-dense", "attention", "conv-experts"])
def test_one_precision_lower_fails_the_layer_tolerance(variables, index):
    _, ref, p, x = _layer_pair(variables, index)
    _, low, _, _ = _layer_pair(variables, index, quant="bf16")
    assert _worst(jax.grad(low, (0, 1))(p, x),
                  jax.grad(ref, (0, 1))(p, x)) > 5 * LAYER_TOL


@pytest.mark.parametrize("attn,moe,remat", [
    ("full", "xla", "none"), ("flash", "pallas", "full")])
def test_model_logits_loss_and_gradients_match_the_reference(variables, attn,
                                                             moe, remat):
    ids, tg = _ids()
    params, stats = variables["params"], variables["batch_stats"]
    m = create_model("lfm2_24b_a2b_tiny", attn_impl=attn, moe_impl=moe,
                     remat_policy=remat)
    logits = m.apply(variables, ids)
    ref = R.inference_forward(params, stats, ids, SPEC)
    assert logits.shape == (2, 40, 512) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, ref, atol=2e-5)
    (loss, _), g = jax.value_and_grad(
        lambda p: m.apply({"params": p, "batch_stats": stats}, ids, tg,
                          method="sequence_loss"), has_aux=True)(params)
    # the reference finds the configuration's own buffers where it is
    # handed none (drivers/train_seq.py hands it none)
    rl, rg, _, _ = R.loss_and_grads(params, {}, ids, tg, SPEC)
    assert abs(float(loss) - float(rl)) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree.leaves(rg)):
        assert _rel(a, b) < 1e-3, (jax.tree_util.keystr(path), _rel(a, b))


def test_the_seeded_bias_is_the_configurations_and_changes_selections(
        variables):
    stats = variables["batch_stats"]
    assert sorted(stats) == ["layers_1", "layers_2", "layers_3", "layers_4"]
    other = W.make_variables(8, *R.param_shapes(SPEC), leaf=R.init_leaf)
    for name, leaf in stats.items():
        bias = np.asarray(leaf["expert_bias"])
        assert bias.shape == (8,) and 0.002 < bias.std() < 0.03
        # whatever the run's seed: a constant of the configuration
        np.testing.assert_array_equal(
            bias, np.asarray(other["batch_stats"][name]["expert_bias"]))
        np.testing.assert_array_equal(
            bias, np.asarray(R.default_stats(SPEC)[name]["expert_bias"]))
    assert not np.array_equal(np.asarray(stats["layers_1"]["expert_bias"]),
                              np.asarray(stats["layers_2"]["expert_bias"]))
    x = jax.random.normal(jax.random.PRNGKey(5), (4000, 64))
    p = variables["params"]["layers_1"]
    with_bias = R.routing_weights(p, stats["layers_1"]["expert_bias"], x,
                                  SPEC)
    without = R.routing_weights(p, jnp.zeros((8,)), x, SPEC)
    moved = np.any((np.asarray(with_bias) > 0) != (np.asarray(without) > 0),
                   axis=1)
    assert 0 < moved.mean() < 0.5


def test_named_scopes_survive_into_the_lowered_program(variables):
    m = create_model("lfm2_24b_a2b_tiny", attn_impl="full")
    ids, tg = _ids(1, 24)
    text = jax.jit(jax.grad(lambda p: m.apply(
        {"params": p, "batch_stats": variables["batch_stats"]}, ids, tg,
        method="sequence_loss")[0])).lower(
            variables["params"]).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("/embed/", "layers_0/.*conv_mix", "layers_0/.*mlp_dense",
                  "layers_1/.*attn_full", "layers_1/.*moe_router",
                  "layers_2/.*moe_dispatch", "layers_3/.*moe_experts",
                  "layers_4/.*moe_combine", "lm_head_loss"):
        assert any(re.search(scope, n) for n in names), scope
    # the cell's trace_groups file every scope under its own name
    groups = CELL["trace_groups"]
    first = lambda n: next((g for g, pat in groups            # noqa: E731
                            if re.search(pat, n)), None)
    found = {first(n) for n in names}
    assert {"conv_mix", "attn_full", "mlp_dense", "moe_router",
            "moe_dispatch", "moe_experts", "moe_combine", "lm_head_loss",
            "embed"} <= found
    assert [g for g, _ in groups][-2:] == ["layers_other", "optimizer"]


# ---- three optimizer steps through the one train step ----------------------

def _cfg():
    from deepfake_detection_tpu.config import TrainConfig
    return TrainConfig.from_args(
        ["--model", "lfm2_24b_a2b_tiny", "--model-version", "",
         "--dataset", "synthetic-tokens", "--seq-len", "40", "-b", "2",
         "--grad-accum", "2", "--opt", "adamw", "--opt-beta2", "0.95",
         "--lr", "1e-3", "--weight-decay", "1e-4", "--clip-grad", "1.0",
         "--compute-dtype", "float32", "--attn-impl", "full"])


@pytest.mark.parametrize("grad_accum", [1, 2], ids=["whole", "accumulated"])
def test_three_adamw_steps_match_the_reference(variables, grad_accum):
    from deepfake_detection_tpu.optim import create_optimizer
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_train_step)
    cfg = _cfg()
    model = create_model("lfm2_24b_a2b_tiny", attn_impl="full",
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
        state, metrics = step(state, ids, tg, rng)
        loss, grads, _, _ = R.loss_and_grads(rp, stats0, ids, tg, SPEC)
        rp, ropt, g = O.update(rp, grads, ropt, **kw)
        assert abs(float(metrics["loss"]) - float(loss)) < 2e-5 * (i + 1)
        # four rows of 40 tokens through four expert layers, whole or in
        # two microbatches; the held experts' share of their assignments
        counts = np.asarray(metrics["moe_counts"])
        assert counts[0] == 4 * 40 * 4 and 0 < counts[1] < 2 * counts[0]
        assert counts[2] <= counts[1] <= counts[3] == 2 * counts[2]
        # a pass is a layer of a microbatch: those that took every row
        assert counts.shape == (5,) and 0 <= counts[4] <= 4 * grad_accum
        if i == 0:
            g1 = O.program_first_gradient(state.opt_state, **kw)
            for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g)):
                assert _rel(jnp.asarray(a), b) < 1e-3
    for (path, a), b, z in zip(
            jax.tree_util.tree_flatten_with_path(state.params)[0],
            jax.tree.leaves(rp), jax.tree.leaves(p0)):
        assert _rel(a - z, b - z) < 2e-2, jax.tree_util.keystr(path)
    # the buffer is no parameter: training leaves it as it was
    for a, b in zip(jax.tree.leaves(state.batch_stats),
                    jax.tree.leaves(stats0)):
        np.testing.assert_array_equal(np.asarray(a), b)


@pytest.mark.parametrize("to_held, full", [(True, 4), (False, 0)],
                         ids=["every-token-to-held", "none-to-held"])
def test_the_fifth_count_is_the_passes_that_took_every_row(variables,
                                                           to_held, full):
    """A selection bias that sends every token's two selections to the two
    held experts overflows the first capacity (half of the rows) in each of
    the four expert layers; one that keeps them all away fills none."""
    model = create_model("lfm2_24b_a2b_tiny", attn_impl="full")
    first, count = model.held
    held = (np.arange(model.n_experts) >= first) \
        & (np.arange(model.n_experts) < first + count)
    stats = jax.tree.map(
        lambda b: jnp.where(held == to_held, 10.0, 0.0).astype(b.dtype),
        variables["batch_stats"])
    ids, tg = _ids()
    _, mut = model.apply({"params": variables["params"],
                          "batch_stats": stats}, ids, tg, training=True,
                         mutable=["moe_counts"], method="sequence_loss")
    counts = sum(np.asarray(c) for c in jax.tree.leaves(mut["moe_counts"]))
    tokens = 4 * ids.size
    assert counts.tolist()[:2] == [tokens, 2 * tokens if to_held else 0]
    assert counts[4] == full


def test_a_model_that_sows_nothing_adds_no_key_to_the_metrics():
    import optax
    from deepfake_detection_tpu.models import init_model
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_train_step)
    model = create_model("granite4_h_micro_tiny", attn_impl="full")
    tx = optax.sgd(1e-3)
    state = create_train_state(init_model(
        model, jax.random.PRNGKey(0), (1, 8), dtype=jnp.int32), tx)
    ids, tg = _ids(rows=2, l=16)
    for accum in (1, 2):
        _, metrics = make_train_step(model, tx, grad_accum=accum,
                                     donate=False)(
            state, ids, tg, jax.random.PRNGKey(1))
        assert set(metrics) == {"loss", "prec1"}


# ---- the counters -----------------------------------------------------------

def test_the_routing_counts_reach_the_telemetry_at_the_drain(tmp_path):
    """Through ``train_one_epoch``: the device's counts of every step are
    added at the drain, whole steps at a time."""
    from deepfake_detection_tpu.config import TrainConfig
    from deepfake_detection_tpu.runners import train as T
    from deepfake_detection_tpu.train import train_one_epoch
    cfg = TrainConfig.from_args([
        "--model", "lfm2_24b_a2b_tiny", "--model-version", "",
        "--dataset", "synthetic-tokens", "--seq-len", "32", "-b", "1",
        "--grad-accum", "2", "--opt", "adamw", "--attn-impl", "full",
        "--log-interval", "3", "--workers", "1", "--output", str(tmp_path)])
    program = T.build_program(cfg)
    assert program.moe_layers == (0, 4)        # the CPU takes the array form
    assert program.attn_bwd_layers == (0, 0)   # --attn-impl full: no kernel
    state, shardings = T.init_state(program, jax.random.PRNGKey(0))
    train_ds, _ = T.build_datasets(cfg, program.input_size,
                                   vocab_rows=program.model.vocab_rows)
    loader, _ = T.build_loaders(program, train_ds)
    step = T.build_steps(program, shardings)[0]
    telemetry, _, profiler = T.build_telemetry(program, state, loader)
    loader.set_epoch(0)
    train_one_epoch(0, step, state, loader, cfg, jax.random.PRNGKey(1),
                    world_size=program.n_dev, telemetry=telemetry)
    snap = telemetry.snapshot()
    c, steps = snap["counters"], len(loader)
    rows = program.global_batch
    assert c["steps_total"] == steps
    assert c["moe_routed_tokens_total"] == steps * rows * 32 * 4
    assert 0 < c["moe_peak_assignments_total"] \
        <= c["moe_assignments_total"] < 2 * c["moe_routed_tokens_total"]
    assert 0 <= c["moe_full_capacity_passes_total"] <= steps * 4 * 2
    assert 1.0 <= snap["gauges"]["moe_load_peak_to_mean"] <= 2.0
    assert "dfd_train_moe_assignments_total" in telemetry.render_prometheus()
    loader.close()
    if profiler is not None:
        profiler.close()
    telemetry.close()


def test_telemetry_without_a_routed_model_counts_nothing():
    from deepfake_detection_tpu.obs import TrainTelemetry
    t = TrainTelemetry()
    t.on_step(3, 0.0, 0.1)
    snap = t.snapshot()
    for name in ("moe_routed_tokens_total", "moe_assignments_total",
                 "moe_peak_assignments_total",
                 "moe_full_capacity_passes_total"):
        assert snap["counters"][name] == 0
    assert snap["gauges"]["moe_load_peak_to_mean"] == 0
    t.on_routing(100, 50, 30, 60, 0)
    t.on_routing(100, 30, 20, 40, 3)
    snap = t.snapshot()
    assert snap["counters"]["moe_full_capacity_passes_total"] == 3
    assert snap["counters"]["moe_assignments_total"] == 80
    assert snap["counters"]["moe_peak_assignments_total"] == 50
    assert snap["gauges"]["moe_load_peak_to_mean"] == round(40 / 30, 4)


# ---- the normal runner ------------------------------------------------------

def _run(out, epochs, *extra):
    from deepfake_detection_tpu.runners.train import launch_main
    return launch_main([
        "--model", "lfm2_24b_a2b_tiny", "--model-version", "",
        "--dataset", "synthetic-tokens", "--seq-len", "32", "-b", "1",
        "--grad-accum", "2", "--opt", "adamw", "--lr", "1e-3",
        "--weight-decay", "1e-4", "--sched", "step", "--decay-rate", "1.0",
        "--epochs", str(epochs), "--clip-grad", "1.0",
        "--checkpoint-policy", "full", "--attn-impl", "full",
        "--compute-dtype", "float32", "--workers", "2", "--log-interval",
        "4", "--recovery-interval", "0", "--output", str(out), *extra])


def test_runner_trains_saves_restores_and_continues_bit_identically(
        tmp_path, devices):
    from deepfake_detection_tpu.models.helpers import load_state_dict
    whole = _run(tmp_path / "a", 2)
    assert whole["best_metric"] is not None and np.isfinite(whole["loss"])
    run_a = tmp_path / "a" / os.listdir(tmp_path / "a")[0]
    events = [json.loads(line) for line in open(run_a / "telemetry.jsonl")]
    start = next(e for e in events if e.get("event") == "run_start")
    assert (start["moe_kernel_layers"], start["moe_xla_layers"]) == (0, 4)
    last = [e for e in events if "counters" in e][-1]["counters"]
    assert last["moe_routed_tokens_total"] > 0
    _run(tmp_path / "b", 1)
    first = tmp_path / "b" / os.listdir(tmp_path / "b")[0]
    _run(tmp_path / "c", 2, "--resume", str(first / "checkpoint-0.ckpt"))
    a = load_state_dict(str(run_a / "checkpoint-1.ckpt"))
    c = load_state_dict(str(tmp_path / "c" / os.listdir(tmp_path / "c")[0]
                            / "checkpoint-1.ckpt"))
    la, lc = jax.tree.leaves(a["params"]), jax.tree.leaves(c["params"])
    assert len(la) == len(lc) > 40
    assert all(np.array_equal(x, y) for x, y in zip(la, lc))
    # the selection bias travels with the checkpoint, as a buffer
    assert len(jax.tree.leaves(a["batch_stats"])) == 4
