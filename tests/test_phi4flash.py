"""models/phi4flash.py (the SambaY stack) against the plain reference
``benchmark/reference/phi4flash.py`` on seeded weights, at tiny sizes on the
CPU: every mixer, the whole model, the sliced vocabulary, three AdamW steps
through ``make_train_step``, the token datasets and loader, and one tiny
epoch through ``runners/train.py`` with a save, a restore and a bit-identical
continuation."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import weights as W                        # noqa: E402
from benchmark.reference import optim_adamw as O              # noqa: E402
from benchmark.reference import phi4flash as R                # noqa: E402
from deepfake_detection_tpu.losses import next_token_loss     # noqa: E402
from deepfake_detection_tpu.models import create_model        # noqa: E402
from deepfake_detection_tpu.models import phi4flash as P      # noqa: E402

TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "sliding_window": 16,
        "layer_norm_eps": 1e-5, "vocab_size": 512, "num_hidden_layers": 6,
        "assumed": {"d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 4},
        "layout": {"self_periods": 1, "cross_periods": 1}}
SPEC = R.model_spec(TINY)
KINDS = R.schedule(SPEC)


@pytest.fixture(scope="module")
def params():
    return W.make_variables(7, *R.param_shapes(SPEC),
                            gains=R.residual_gains(SPEC),
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


def test_published_model_is_32_layers_in_the_published_schedule_and_3_85b():
    m, n = _count("phi4_mini_flash")
    kinds = P.layer_schedule(m.self_periods, m.cross_periods)
    assert len(kinds) == 32
    assert [kinds.count(k) for k in (P.MAMBA, P.WINDOW, P.FULL, P.GMU,
                                     P.CROSS)] == [9, 8, 1, 7, 7]
    assert kinds[:16] == (P.MAMBA, P.WINDOW) * 8
    assert kinds[16:18] == (P.MAMBA, P.FULL)
    assert kinds[18:] == (P.GMU, P.CROSS) * 7
    assert 3.84e9 < n < 3.86e9, n


def test_the_cut_is_one_period_of_each_part_and_697m():
    m, n = _count("phi4_mini_flash_6l")
    assert P.layer_schedule(m.self_periods, m.cross_periods) == (
        P.MAMBA, P.WINDOW, P.MAMBA, P.FULL, P.GMU, P.CROSS)
    assert m.vocab_rows == 200064 // 8
    assert 6.96e8 < n < 6.98e8, n
    assert (m.d_model, m.n_heads, m.n_kv_heads, m.head_dim, m.d_ff,
            m.window) == (2560, 40, 20, 64, 10240, 512)


# ---- every mixer: forward and gradient against the reference ---------------

@pytest.mark.parametrize("impl", ["full", "flash"])
@pytest.mark.parametrize("layer", range(6), ids=list(KINDS))
def test_each_layer_forward_and_gradient_match_the_reference(params, layer,
                                                             impl):
    kind = KINDS[layer]
    if impl == "flash" and kind in (P.MAMBA, P.GMU):
        pytest.skip("no attention in this layer")
    l = 40
    ks = jax.random.split(jax.random.PRNGKey(layer), 5)
    x = jax.random.normal(ks[0], (l, 64))
    mem = ()
    if kind == P.GMU:
        mem = jax.random.normal(ks[1], (l, 128))
    elif kind == P.CROSS:
        mem = (jax.random.normal(ks[1], (l, 2, 16)),
               jax.random.normal(ks[2], (l, 1, 32)))
    mod = P._Layer(kind=kind, index=layer, d_model=64, n_heads=4,
                   n_kv_heads=2, head_dim=16, d_ff=128, d_inner=128,
                   d_state=16, d_conv=4, dt_rank=4, window=16,
                   attn_impl=impl, scan_chunk=8)
    p = params[f"layers_{layer}"]
    w = jax.random.normal(ks[3], (l, 64))
    batched = lambda t: jax.tree.map(lambda a: a[None], t)      # noqa: E731

    def prog(p, x, mem):
        y, out = mod.apply({"params": p}, x[None], False, batched(mem))
        return jnp.sum(y[0] * w) + sum(jnp.sum(o) for o in
                                       jax.tree.leaves(out))

    def ref(p, x, mem):
        y, out = R.layer_forward(p, x, mem, SPEC, kind, layer)
        return jnp.sum(y * w) + sum(jnp.sum(o) for o in jax.tree.leaves(out))

    np.testing.assert_allclose(prog(p, x, mem), ref(p, x, mem), rtol=1e-4)
    g1 = jax.grad(prog, (0, 1, 2))(p, x, mem)
    g2 = jax.grad(ref, (0, 1, 2))(p, x, mem)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g1)[0],
                            jax.tree.leaves(g2)):
        assert _rel(a, b) < 2e-4, (jax.tree_util.keystr(path), _rel(a, b))


@pytest.mark.parametrize("impl,remat", [("full", "none"), ("flash", "full")])
def test_model_logits_loss_and_gradients_match_the_reference(params, impl,
                                                             remat):
    ids, tg = _ids()
    m = create_model("phi4_mini_flash_tiny", attn_impl=impl,
                     remat_policy=remat)
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
        m = create_model("phi4_mini_flash_tiny", attn_impl="full", dtype=dtype)
        return jax.value_and_grad(lambda p: m.apply(
            {"params": p}, ids, tg, method="sequence_loss")[0])
    assert_the_op_is_the_expression_it_replaced(loss_and_grads, params, P,
                                                monkeypatch)

def test_the_slice_ties_to_the_model(params):
    """With ids from the slice, the cut's logits are the columns [0, V/8) of
    the uncut model's: the rows held are the uncut model's first rows."""
    whole = create_model("phi4_mini_flash_tiny", attn_impl="full")
    cut = create_model("phi4_mini_flash_tiny", attn_impl="full",
                       vocab_rows=64)
    ids, _ = _ids(vocab=64)
    p_cut = dict(params, embed={"embedding":
                                params["embed"]["embedding"][:64]})
    a = whole.apply({"params": params}, ids)
    b = cut.apply({"params": p_cut}, ids)
    assert b.shape[-1] == 64
    np.testing.assert_allclose(b, a[..., :64], atol=1e-6)


def test_named_scopes_survive_into_the_lowered_program(params):
    m = create_model("phi4_mini_flash_tiny", attn_impl="full")
    ids, tg = _ids(1, 24)
    import re
    text = jax.jit(jax.grad(lambda p: m.apply(
        {"params": p}, ids, tg, method="sequence_loss")[0])).lower(
            params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("/embed/", "layers_0/.*mamba_proj", "layers_0/.*mamba_conv",
                  "layers_0/.*mamba_scan", "layers_1/.*attn_window",
                  "layers_3/.*attn_full", "layers_4/gmu",
                  "layers_5/.*attn_cross", "layers_5/mlp", "lm_head_loss"):
        assert any(re.search(scope, n) for n in names), scope


# ---- the loss ---------------------------------------------------------------

@pytest.mark.parametrize("l,chunk", [(40, 16), (32, 32), (7, 64)])
def test_chunked_next_token_loss_equals_the_whole_one(l, chunk):
    k = jax.random.split(jax.random.PRNGKey(l), 3)
    h = jax.random.normal(k[0], (2, l, 16))
    e = jax.random.normal(k[1], (50, 16))
    t = jax.random.randint(k[2], (2, l), 0, 50).at[:, -1].set(-1)
    loss, acc = next_token_loss(h, e, t, chunk=chunk)
    logits = jnp.einsum("bld,vd->blv", h, e)
    lp = jax.nn.log_softmax(logits, -1)
    valid = t >= 0
    nll = -jnp.take_along_axis(lp, jnp.maximum(t, 0)[..., None], -1)[..., 0]
    np.testing.assert_allclose(loss, jnp.sum(nll * valid) / valid.sum(),
                               rtol=1e-5)
    hit = (jnp.argmax(logits, -1) == t) & valid
    np.testing.assert_allclose(acc, 100.0 * hit.sum() / valid.sum(),
                               rtol=1e-5)
    g = jax.grad(lambda h_: next_token_loss(h_, e, t, chunk=chunk)[0])(h)
    g2 = jax.grad(lambda h_: jnp.sum(-jnp.take_along_axis(
        jax.nn.log_softmax(jnp.einsum("bld,vd->blv", h_, e), -1),
        jnp.maximum(t, 0)[..., None], -1)[..., 0] * valid) / valid.sum())(h)
    np.testing.assert_allclose(g, g2, atol=1e-6)


def test_row_weights_mask_padded_rows_of_an_eval_batch():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(k[0], (3, 8, 16))
    e = jax.random.normal(k[1], (20, 16))
    t = jax.random.randint(k[2], (3, 8), 0, 20)
    a, _ = next_token_loss(h, e, t, weight=jnp.asarray([1.0, 1.0, 0.0]))
    b, _ = next_token_loss(h[:2], e, t[:2])
    np.testing.assert_allclose(a, b, rtol=1e-6)


# ---- three optimizer steps through the one train step ----------------------

def _cfg(**kw):
    from deepfake_detection_tpu.config import TrainConfig
    return TrainConfig.from_args(
        ["--model", "phi4_mini_flash_tiny", "--model-version", "",
         "--dataset", "synthetic-tokens", "--seq-len", "40", "-b", "2",
         "--opt", "adamw", "--opt-beta2", "0.95", "--lr", "1e-3",
         "--weight-decay", "1e-4",
         "--clip-grad", "1.0", "--compute-dtype", "float32",
         "--attn-impl", "full"] + [str(a) for kv in kw.items() for a in kv])


@pytest.mark.parametrize("on_mesh", [False, True], ids=["jit", "mesh"])
def test_three_adamw_steps_match_the_reference(params, on_mesh, devices):
    from deepfake_detection_tpu.optim import create_optimizer
    from deepfake_detection_tpu.parallel import make_mesh
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_train_step)
    cfg = _cfg()
    model = create_model("phi4_mini_flash_tiny", attn_impl="full",
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
    rp, ropt = jax.tree.map(jnp.asarray, p0), None
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


def test_eval_step_reports_the_sequence_loss_and_no_logits(params):
    from deepfake_detection_tpu.train import make_eval_step
    from deepfake_detection_tpu.train.state import TrainState
    model = create_model("phi4_mini_flash_tiny", attn_impl="full")
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       batch_stats={}, opt_state=())
    ids, tg = _ids(3)
    out = make_eval_step(model)(state, ids, tg,
                                jnp.asarray([1.0, 1.0, 0.0]))
    rl, _, _, _ = R.loss_and_grads(params, {}, ids[:2], tg[:2], SPEC)
    assert set(out) == {"loss", "prec1", "count"}
    assert abs(float(out["loss"]) - float(rl)) < 1e-5
    assert float(out["count"]) == 2.0


# ---- data -------------------------------------------------------------------

def test_synthetic_token_rows_are_seeded_zipf_documents():
    from deepfake_detection_tpu.data import SyntheticTokenDataset
    ds = SyntheticTokenDataset(16, 4096, 512, seed=3)
    ids, tg = ds[5]
    ids2, _ = ds[5]
    assert ids.dtype == np.int32 and ids.shape == (4096,)
    assert np.array_equal(ids, ids2) and not np.array_equal(ids, ds[6][0])
    assert ids.min() >= 0 and ids.max() < 512
    assert np.array_equal(tg[:-1], ids[1:]) and tg[-1] == -1
    # Zipf, s = 1: id 0 is drawn about 1 / H(512) = 14.6% of the time
    assert 0.10 < np.mean(ids == 0) < 0.19


def test_token_file_rows_are_read_as_documents(tmp_path):
    from deepfake_detection_tpu.data import TokenFileDataset
    flat = np.arange(50, dtype=np.int32) % 40
    np.save(tmp_path / "rows.npy", flat)
    flat.tofile(tmp_path / "rows.bin")
    for name in ("rows.npy", "rows.bin"):
        ds = TokenFileDataset(str(tmp_path / name), 16, 40)
        assert len(ds) == 3                       # the ragged tail is dropped
        ids, tg = ds[1]
        assert np.array_equal(ids, flat[16:32])
        assert np.array_equal(tg[:-1], flat[17:32]) and tg[-1] == -1
    with pytest.raises(ValueError, match="outside"):
        TokenFileDataset(str(tmp_path / "rows.npy"), 16, 30)[1]


def test_token_loader_is_the_host_and_device_loader_with_their_counters():
    from deepfake_detection_tpu.data import (DeviceLoader, HostLoader,
                                             SyntheticTokenDataset,
                                             create_token_loader)
    ds = SyntheticTokenDataset(8, 32, 100, seed=1)
    loader = create_token_loader(ds, 2, is_training=True, num_workers=2,
                                 seed=5)
    assert isinstance(loader, DeviceLoader)
    assert isinstance(loader.loader, HostLoader)
    loader.set_epoch(0)
    batches = list(loader)
    assert len(batches) == 4
    x, y = batches[0]
    assert x.shape == (2, 32) and x.dtype == jnp.int32
    assert y.shape == (2, 32) and y.dtype == jnp.int32
    assert np.array_equal(np.asarray(y)[:, :-1], np.asarray(x)[:, 1:])
    rows = {tuple(np.asarray(ds[i][0])) for i in range(8)}
    assert all(tuple(r) in rows for b in batches for r in np.asarray(b[0]))
    assert loader.stats.batches == 4 and loader.loader.stats.batches == 4
    assert loader.stats.stage_s > 0 and loader.loader.stats.collate_s > 0
    loader.set_epoch(0)
    again = list(loader)
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(batches, again))
    loader.close()


def test_image_collate_still_stacks_uint8_and_int64_labels():
    from deepfake_detection_tpu.data import fast_collate
    imgs, t = fast_collate([(np.full((4, 4, 3), 300 % 256, np.int64), 1),
                            (np.zeros((4, 4, 3), np.int64), 0)])
    assert imgs.dtype == np.uint8 and t.dtype == np.int64
    assert t.tolist() == [1, 0]


def test_telemetry_counts_the_tokens_of_the_steps_dispatched():
    from deepfake_detection_tpu.obs import TrainTelemetry
    t = TrainTelemetry()
    for _ in range(2):
        t.on_step(2, 0.0, 0.1, tokens=2 * 48)
    t.on_step(3, 0.0, 0.1)                          # an image batch
    c = t.snapshot()["counters"]
    assert c["train_tokens_total"] == 192 and c["steps_total"] == 3
    assert "dfd_train_train_tokens_total" in t.render_prometheus()


def test_telemetry_counts_the_attention_tiles_by_the_models_census():
    """attn_tiles_visited_total advances by rows x the sequence model's
    census each step, and stays 0 for an image model."""
    from deepfake_detection_tpu.models import create_model
    from deepfake_detection_tpu.obs import TrainTelemetry
    from deepfake_detection_tpu.ops.flash_attention import tile_census
    model = create_model("phi4_mini_flash_tiny")
    # 2,100 tokens at the layers' own blocks.  Under the window of 16, blocks
    # of 512: 5 q blocks by 3 tiles, each block's own tile and (but for the
    # first block) the one before it visited.  The full and the cross layer,
    # blocks of 1024: 3 by 3, the diagonal and the 3 tiles below it visited
    window = tile_census(2100, 512, 512, True, 16)["fwd"]
    assert (window["cells"], window["visited"]) == (15, 9)
    full = tile_census(2100, 1024, 1024, True)["dkv"]
    assert (full["cells"], full["visited"]) == (9, 6)
    # 4 query heads, the forward and the one fused backward, one window, one
    # full and one cross layer
    visited = model.attn_tiles_visited(2100)
    assert visited == 4 * 2 * (9 + 6 + 6)
    assert model.attn_tiles_visited(300) == 4 * 2 * 3    # one tile a layer
    assert model.attn_bwd_layers(2100) == (3, 0)
    assert model.clone(attn_impl="full").attn_bwd_layers(2100) == (0, 0)
    assert model.clone(attn_impl="full").attn_tiles_visited(2100) == 0
    t = TrainTelemetry(attn_tiles_per_sample=visited)
    for _ in range(3):
        t.on_step(2, 0.0, 0.1, tokens=2 * 2100)
    assert t.snapshot()["counters"]["attn_tiles_visited_total"] == \
        3 * 2 * visited
    assert "dfd_train_attn_tiles_visited_total" in t.render_prometheus()
    image = TrainTelemetry()
    image.on_step(3, 0.0, 0.1)
    assert image.snapshot()["counters"]["attn_tiles_visited_total"] == 0


# ---- the normal runner ------------------------------------------------------

def _run(out, epochs, *extra):
    from deepfake_detection_tpu.runners.train import launch_main
    return launch_main([
        "--model", "phi4_mini_flash_tiny", "--model-version", "",
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
    assert len(la) == len(lc) > 50
    assert all(np.array_equal(x, y) for x, y in zip(la, lc))
    assert "batch_stats" not in a or jax.tree.leaves(a["batch_stats"]) == []
