"""models/granite4h.py (Mamba-2 layers with NoPE grouped attention, muP
multipliers) against the plain reference ``benchmark/reference/granite4h.py``
on seeded weights, at tiny sizes on the CPU: both mixers, the whole model,
the sliced vocabulary and the cut schedule, three AdamW steps through
``make_train_step``, the scan's counter, and one tiny epoch through
``runners/train.py`` with a save, a restore and a bit-identical
continuation.

Tolerances, and why each: everything here is float32 on the CPU, where the
program and the reference differ by the order of their sums alone (the dual
form's chunked products against 40 sequential steps; flash attention's
running softmax against a whole one).  A layer's outputs and gradients agree
to 2e-4 of their norm, the model's logits to 2e-5 absolute (they are of
order 0.1), the model's gradients to 1e-3 (ten layers of it), three steps'
parameter changes to 2e-2 (Adam divides by the gradient's own magnitude, so
a leaf with a small gradient amplifies its rounding).
``test_one_precision_lower_fails_the_layer_tolerance`` shows that the layer
tolerance is no wider than it may be: the same comparison with the scan's or
the attention's operands rounded to bfloat16 fails it.
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
from benchmark.reference import granite4h as R                # noqa: E402
from benchmark.reference import optim_adamw as O              # noqa: E402
from deepfake_detection_tpu.losses import next_token_loss     # noqa: E402
from deepfake_detection_tpu.models import create_model        # noqa: E402
from deepfake_detection_tpu.models import granite4h as G      # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "granite4_h_micro_10l.json")) as _f:
    CELL = json.load(_f)
# the cell's configuration at the registry's tiny widths
TINY = dict(CELL, hidden_size=64, shared_intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=1, mamba_n_heads=4,
            mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=8,
            vocab_size=512)
SPEC = R.model_spec(TINY)
KINDS = R.schedule(SPEC)
LAYER_TOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return W.make_variables(7, *R.param_shapes(SPEC),
                            leaf=R.init_leaf)["params"]


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
    return m, sum(x.size for x in jax.tree.leaves(s["params"]))


def test_published_model_follows_the_published_layer_types_and_is_3_19b():
    m, n = _count("granite4_h_micro")
    assert list(m.layer_types) == CELL["layer_types"]
    assert len(m.layer_types) == 40
    assert [i for i, k in enumerate(m.layer_types) if k == G.ATTENTION] == \
        [5, 15, 25, 35]
    assert m.vocab_rows == 100352
    assert n == 3_191_396_096


def test_the_cut_is_the_first_period_at_published_widths_and_772m():
    m, n = _count("granite4_h_micro_10l")
    whole = create_model("granite4_h_micro")
    assert m.layer_types == whole.layer_types[:10] == \
        tuple(CELL["layer_types"][:CELL["num_hidden_layers"]])
    assert m.vocab_rows == 100352 // 8 == CELL["vocab_size"]
    assert n == 772_160_448
    for field, key in (("d_model", "hidden_size"),
                       ("d_ff", "shared_intermediate_size"),
                       ("n_heads", "num_attention_heads"),
                       ("n_kv_heads", "num_key_value_heads"),
                       ("ssm_heads", "mamba_n_heads"),
                       ("ssm_head_dim", "mamba_d_head"),
                       ("d_state", "mamba_d_state"),
                       ("d_conv", "mamba_d_conv"),
                       ("chunk", "mamba_chunk_size"),
                       ("embedding_multiplier", "embedding_multiplier"),
                       ("residual_multiplier", "residual_multiplier"),
                       ("attention_multiplier", "attention_multiplier"),
                       ("logits_scaling", "logits_scaling"),
                       ("eps", "rms_norm_eps")):
        assert getattr(m, field) == getattr(whole, field) == CELL[key], field
    assert m.head_dim == CELL["hidden_size"] // CELL["num_attention_heads"]
    assert m.ssm_heads * m.ssm_head_dim == \
        CELL["mamba_expand"] * CELL["hidden_size"]


def test_the_tiny_model_is_the_cuts_schedule():
    tiny = create_model("granite4_h_micro_tiny")
    assert tiny.layer_types == create_model("granite4_h_micro_10l"
                                            ).layer_types == KINDS


# ---- both mixers: forward and gradient against the reference ---------------

def _layer(kind, **kw):
    return G._Layer(
        kind=kind, d_model=64, n_heads=4, n_kv_heads=1, head_dim=16,
        d_ff=128, ssm_heads=4, ssm_head_dim=32, d_state=16, d_conv=4,
        chunk=8, residual_multiplier=0.22, attention_multiplier=0.015625,
        eps=1e-5, **kw)


def _layer_pair(params, kind, quant=None, **kw):
    """The program's and the reference's scalar function of one layer."""
    layer = KINDS.index(kind)
    p = params[f"layers_{layer}"]
    ks = jax.random.split(jax.random.PRNGKey(layer), 2)
    x, w = (jax.random.normal(k, (40, 64)) for k in ks)
    mod = _layer(kind, **kw)
    prog = lambda p, x: jnp.sum(                              # noqa: E731
        mod.apply({"params": p}, x[None], False)[0] * w)
    ref = lambda p, x: jnp.sum(                               # noqa: E731
        R.layer_forward(p, x, SPEC, kind, quant) * w)
    return prog, ref, p, x


def _worst(g1, g2):
    return max(_rel(a, b) for a, b in zip(jax.tree.leaves(g1),
                                          jax.tree.leaves(g2)))


@pytest.mark.parametrize("kind,kw", [
    (G.MAMBA, {"scan_impl": "xla"}), (G.MAMBA, {"scan_impl": "pallas"}),
    (G.ATTENTION, {"attn_impl": "full"}),
    (G.ATTENTION, {"attn_impl": "flash"})],
    ids=["mamba-xla", "mamba-pallas", "attention-full", "attention-flash"])
def test_each_layer_forward_and_gradient_match_the_reference(params, kind,
                                                             kw):
    prog, ref, p, x = _layer_pair(params, kind, **kw)
    np.testing.assert_allclose(prog(p, x), ref(p, x), rtol=1e-4)
    assert _worst(jax.grad(prog, (0, 1))(p, x),
                  jax.grad(ref, (0, 1))(p, x)) < LAYER_TOL


@pytest.mark.parametrize("kind", [G.MAMBA, G.ATTENTION])
def test_one_precision_lower_fails_the_layer_tolerance(params, kind):
    """The reference with the scan's (or the attention's) operands rounded
    to bfloat16 is further from the reference than the tolerance the
    program is held to."""
    _, ref, p, x = _layer_pair(params, kind)
    _, low, _, _ = _layer_pair(params, kind, quant="bf16")
    assert _worst(jax.grad(low, (0, 1))(p, x),
                  jax.grad(ref, (0, 1))(p, x)) > 5 * LAYER_TOL


@pytest.mark.parametrize("attn,scan,remat", [
    ("full", "xla", "none"), ("flash", "pallas", "full")])
def test_model_logits_loss_and_gradients_match_the_reference(params, attn,
                                                             scan, remat):
    ids, tg = _ids()
    m = create_model("granite4_h_micro_tiny", attn_impl=attn,
                     scan_impl=scan, remat_policy=remat)
    logits = m.apply({"params": params}, ids)
    ref = R.inference_forward(params, {}, ids, SPEC)
    assert logits.shape == (2, 40, 512) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, ref, atol=2e-5)
    (loss, _), g = jax.value_and_grad(
        lambda p: m.apply({"params": p}, ids, tg, method="sequence_loss"),
        has_aux=True)(params)
    rl, rg, _, _ = R.loss_and_grads(params, {}, ids, tg, SPEC)
    assert abs(float(loss) - float(rl)) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree.leaves(rg)):
        assert _rel(a, b) < 1e-3, (jax.tree_util.keystr(path), _rel(a, b))


def test_the_convolution_op_is_the_expression_it_replaced(params,
                                                          monkeypatch):
    """ops/causal_conv.py in the tiny model against the sum of shifted
    slices the model wrote before PR 31."""
    from test_causal_conv import assert_the_op_is_the_expression_it_replaced
    ids, tg = _ids()

    def loss_and_grads(dtype):
        m = create_model("granite4_h_micro_tiny", attn_impl="full",
                         dtype=dtype)
        return jax.value_and_grad(lambda p: m.apply(
            {"params": p}, ids, tg, method="sequence_loss")[0])
    assert_the_op_is_the_expression_it_replaced(loss_and_grads, params,
                                                G, monkeypatch)

def test_the_slice_ties_to_the_model(params):
    """With ids from the slice, the cut's logits are the columns [0, V/8) of
    the uncut model's: the rows held are the uncut model's first rows."""
    whole = create_model("granite4_h_micro_tiny", attn_impl="full")
    cut = create_model("granite4_h_micro_tiny", attn_impl="full",
                       vocab_rows=64)
    ids, _ = _ids(vocab=64)
    p_cut = dict(params, embed={"embedding":
                                params["embed"]["embedding"][:64]})
    a = whole.apply({"params": params}, ids)
    b = cut.apply({"params": p_cut}, ids)
    assert b.shape[-1] == 64
    np.testing.assert_allclose(b, a[..., :64], atol=1e-6)


def test_the_first_ten_layers_of_a_longer_schedule_are_the_cuts(params):
    """A twenty-layer model whose later layers add nothing (their last
    projections zeroed) gives the ten-layer cut's logits from the same
    first ten layers' weights: the cut is a prefix, not another model."""
    long = create_model("granite4_h_micro_tiny", attn_impl="full",
                        layer_types=G.published_layer_types(20))
    assert long.layer_types[:10] == KINDS and long.layer_types[15] == \
        G.ATTENTION
    ids, _ = _ids(1, 24)
    fresh = long.init(jax.random.PRNGKey(3), ids)["params"]
    p = dict(fresh, **params)
    for i in range(10, 20):
        p[f"layers_{i}"] = dict(p[f"layers_{i}"])
        for name in ("out_proj", "down"):
            p[f"layers_{i}"][name] = jax.tree.map(
                jnp.zeros_like, p[f"layers_{i}"][name])
    cut = create_model("granite4_h_micro_tiny", attn_impl="full")
    np.testing.assert_allclose(long.apply({"params": p}, ids),
                               cut.apply({"params": params}, ids),
                               atol=1e-6)


def test_named_scopes_survive_into_the_lowered_program(params):
    m = create_model("granite4_h_micro_tiny", attn_impl="full")
    ids, tg = _ids(1, 24)
    text = jax.jit(jax.grad(lambda p: m.apply(
        {"params": p}, ids, tg, method="sequence_loss")[0])).lower(
            params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("/embed/", "layers_0/.*ssd_proj", "layers_0/.*ssd_conv",
                  "layers_0/.*ssd_scan", "layers_0/.*ssd_norm",
                  "layers_5/.*attn_full", "layers_5/mlp", "layers_9/mlp",
                  "lm_head_loss"):
        assert any(re.search(scope, n) for n in names), scope
    # the cell's trace_groups file every scope under its own name
    groups = CELL["trace_groups"]
    first = lambda n: next((g for g, pat in groups            # noqa: E731
                            if re.search(pat, n)), None)
    found = {first(n) for n in names}
    assert {"ssd_proj", "ssd_conv", "ssd_scan", "ssd_norm", "attn_full",
            "mlp", "lm_head_loss", "embed"} <= found


# ---- the loss ---------------------------------------------------------------

def test_the_loss_scales_the_logits():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(k[0], (2, 24, 16))
    e = jax.random.normal(k[1], (50, 16))
    t = jax.random.randint(k[2], (2, 24), 0, 50).at[:, -1].set(-1)
    loss, acc = next_token_loss(h, e, t, chunk=8, logit_scale=0.125)
    lp = jax.nn.log_softmax(jnp.einsum("bld,vd->blv", h, e) / 8.0, -1)
    valid = t >= 0
    nll = -jnp.take_along_axis(lp, jnp.maximum(t, 0)[..., None], -1)[..., 0]
    np.testing.assert_allclose(loss, jnp.sum(nll * valid) / valid.sum(),
                               rtol=1e-5)
    same, _ = next_token_loss(h / 8.0, e, t, chunk=8)
    assert float(same) == float(loss) and 0.0 <= float(acc) <= 100.0
    g = jax.grad(lambda h_: next_token_loss(h_, e, t, chunk=8,
                                            logit_scale=0.125)[0])(h)
    g2 = jax.grad(lambda h_: jnp.sum(-jnp.take_along_axis(
        jax.nn.log_softmax(jnp.einsum("bld,vd->blv", h_, e) / 8.0, -1),
        jnp.maximum(t, 0)[..., None], -1)[..., 0] * valid) / valid.sum())(h)
    np.testing.assert_allclose(g, g2, atol=1e-6)


# ---- three optimizer steps through the one train step ----------------------

def _cfg():
    from deepfake_detection_tpu.config import TrainConfig
    return TrainConfig.from_args(
        ["--model", "granite4_h_micro_tiny", "--model-version", "",
         "--dataset", "synthetic-tokens", "--seq-len", "40", "-b", "2",
         "--opt", "adamw", "--opt-beta2", "0.95", "--lr", "1e-3",
         "--weight-decay", "1e-4", "--clip-grad", "1.0",
         "--compute-dtype", "float32", "--attn-impl", "full"])


@pytest.mark.parametrize("on_mesh", [False, True], ids=["jit", "mesh"])
def test_three_adamw_steps_match_the_reference(params, on_mesh, devices):
    from deepfake_detection_tpu.optim import create_optimizer
    from deepfake_detection_tpu.parallel import make_mesh
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_train_step)
    cfg = _cfg()
    model = create_model("granite4_h_micro_tiny", attn_impl="full",
                         remat_policy="full")
    tx = create_optimizer(cfg, learning_rate=cfg.lr)
    p0 = jax.tree.map(np.asarray, params)
    state = create_train_state(
        {"params": jax.tree.map(jnp.asarray, p0)}, tx)
    assert jax.tree.leaves(state.batch_stats) == []
    mesh = make_mesh((2,), ("data",), devices=devices[:2]) if on_mesh \
        else None
    step = make_train_step(model, tx, mesh=mesh, clip_grad=cfg.clip_grad)
    kw = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
              clip=1.0)
    rp = jax.tree.map(jnp.asarray, p0)
    ropt = O.init(rp)
    rng = jax.random.PRNGKey(0)
    for i in range(3):
        ids, tg = _ids(seed=10 + i)
        state, metrics = step(state, ids, tg, rng)
        loss, grads, _, _ = R.loss_and_grads(rp, {}, ids, tg, SPEC)
        rp, ropt, g = O.update(rp, grads, ropt, **kw)
        assert abs(float(metrics["loss"]) - float(loss)) < 2e-5 * (i + 1)
        assert 0.0 <= float(metrics["prec1"]) <= 100.0
        if i == 0:
            g1 = O.program_first_gradient(state.opt_state, **kw)
            for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g)):
                assert _rel(jnp.asarray(a), b) < 1e-3
    for (path, a), b, z in zip(
            jax.tree_util.tree_flatten_with_path(state.params)[0],
            jax.tree.leaves(rp), jax.tree.leaves(p0)):
        assert _rel(a - z, b - z) < 2e-2, jax.tree_util.keystr(path)


# ---- the counter ------------------------------------------------------------

def test_telemetry_counts_the_scans_chunks_by_the_models_census():
    """ssd_chunks_total advances by rows x the model's census each step, and
    stays 0 for a model without the scan."""
    from deepfake_detection_tpu.obs import TrainTelemetry
    cell = create_model("granite4_h_micro_10l")
    assert cell.ssd_chunks(16384) == 9 * 64 == 576
    assert cell.ssd_chunks(16385) == 9 * 65 and cell.ssd_chunks(100) == 9
    tiny = create_model("granite4_h_micro_tiny")
    assert tiny.ssd_chunks(40) == 9 * 5
    assert create_model("granite4_h_micro").ssd_chunks(16384) == 36 * 64
    t = TrainTelemetry(ssd_chunks_per_sample=tiny.ssd_chunks(40),
                       attn_tiles_per_sample=tiny.attn_tiles_visited(40))
    for _ in range(3):
        t.on_step(2, 0.0, 0.1, tokens=2 * 40)
    c = t.snapshot()["counters"]
    assert c["ssd_chunks_total"] == 3 * 2 * 45
    # one attention layer, four query heads, the forward and the fused
    # backward, one tile each
    assert c["attn_tiles_visited_total"] == 3 * 2 * 4 * 2
    assert tiny.attn_bwd_layers(40) == (1, 0)
    assert cell.attn_bwd_layers(16384) == (1, 0)
    assert cell.attn_bwd_layers(32769) == (0, 1)    # a row past the budget
    assert "dfd_train_ssd_chunks_total" in t.render_prometheus()
    other = TrainTelemetry()
    other.on_step(3, 0.0, 0.1)
    assert other.snapshot()["counters"]["ssd_chunks_total"] == 0


def test_the_runner_hands_the_telemetry_both_censuses(tmp_path):
    from deepfake_detection_tpu.config import TrainConfig
    from deepfake_detection_tpu.runners import train as T
    cfg = TrainConfig.from_args([
        "--model", "granite4_h_micro_tiny", "--model-version", "",
        "--dataset", "synthetic-tokens", "--seq-len", "32", "-b", "1",
        "--opt", "adamw", "--output", str(tmp_path)])
    program = T.build_program(cfg)
    state, _ = T.init_state(program, jax.random.PRNGKey(0))
    train_ds, _ = T.build_datasets(cfg, program.input_size,
                                   vocab_rows=program.model.vocab_rows)
    loader, _ = T.build_loaders(program, train_ds)
    telemetry, _, profiler = T.build_telemetry(program, state, loader)
    assert telemetry.ssd_chunks_per_sample == \
        program.model.ssd_chunks(32) == 9 * 4
    assert telemetry.attn_tiles_per_sample == \
        program.model.attn_tiles_visited(32) > 0
    loader.close()
    if profiler is not None:
        profiler.close()
    telemetry.close()


# ---- the normal runner ------------------------------------------------------

def _run(out, epochs, *extra):
    from deepfake_detection_tpu.runners.train import launch_main
    return launch_main([
        "--model", "granite4_h_micro_tiny", "--model-version", "",
        "--dataset", "synthetic-tokens", "--seq-len", "32", "-b", "1",
        "--opt", "adamw", "--lr", "1e-3", "--weight-decay", "1e-4",
        "--sched", "step", "--decay-rate", "1.0", "--epochs", str(epochs),
        "--clip-grad", "1.0", "--checkpoint-policy", "full",
        "--attn-impl", "full", "--compute-dtype", "float32", "--workers",
        "2", "--log-interval", "4", "--recovery-interval", "0",
        "--output", str(out), *extra])


def test_runner_trains_saves_restores_and_continues_bit_identically(
        tmp_path, devices):
    from deepfake_detection_tpu.models.helpers import load_state_dict
    whole = _run(tmp_path / "a", 2)
    assert whole["best_metric"] is not None and np.isfinite(whole["loss"])
    _run(tmp_path / "b", 1)
    first = tmp_path / "b" / os.listdir(tmp_path / "b")[0]
    assert (first / "summary.csv").is_file()
    _run(tmp_path / "c", 2, "--resume", str(first / "checkpoint-0.ckpt"))
    a = load_state_dict(str(tmp_path / "a" / os.listdir(tmp_path / "a")[0]
                            / "checkpoint-1.ckpt"))
    c = load_state_dict(str(tmp_path / "c" / os.listdir(tmp_path / "c")[0]
                            / "checkpoint-1.ckpt"))
    la, lc = jax.tree.leaves(a["params"]), jax.tree.leaves(c["params"])
    assert len(la) == len(lc) > 80
    assert all(np.array_equal(x, y) for x, y in zip(la, lc))
    assert "batch_stats" not in a or jax.tree.leaves(a["batch_stats"]) == []
