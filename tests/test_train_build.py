"""The seam of ``runners/train.py``: one way from a TrainConfig to a program.

``main`` is ``build_program`` → ``init_state`` → ``build_loaders`` →
``build_steps`` → ``build_telemetry`` and the epoch loop.  The benchmark's
drivers (``benchmark/drivers/train.py:Built``, ``train_tokens.py:TokenBuilt``,
which ``train_seq.py`` shares) still spell that set-up out themselves; until they call the builder (ROADMAP
D11) these tests hold the two to the same lowered step and the same batches,
for each benchmark configuration's own ``train_flags`` cut to a CPU size.
"""

import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfake_detection_tpu.config import TrainConfig
from deepfake_detection_tpu.data import (SyntheticDataset,
                                         SyntheticTokenDataset)
from deepfake_detection_tpu.models import init_model
from deepfake_detection_tpu.runners import train as T
from deepfake_detection_tpu.train import resume_position

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:            # the drivers import as ``benchmark.*``
    sys.path.insert(0, REPO)

# configuration -> what cuts its train_flags to a CPU size (argparse keeps
# the last occurrence of a flag) and what the drivers' asserts read
CUTS = {
    "flagship_v4_600": (["--input-size-v2", "12,64,64", "-b", "2"],
                        {"input_size": [12, 64, 64]}),
    "effnet_b4_380": (["--input-size-v2", "3,64,64", "-b", "2"],
                      {"input_size": [3, 64, 64]}),
    "phi4_mini_flash_6l": (["--model", "phi4_mini_flash_tiny", "--seq-len",
                            "64"], {"train": {"seq_len": 64}}),
    "granite4_h_micro_10l": (["--model", "granite4_h_micro_tiny",
                              "--seq-len", "64"],
                             {"train": {"seq_len": 64}}),
    # one pass a step here: the record's global batch is rows x devices
    "lfm2_24b_a2b_5l": (["--model", "lfm2_24b_a2b_tiny", "--seq-len", "64",
                         "--grad-accum", "1"], {"train": {"seq_len": 64}}),
    "glm47_flash_5l": (["--model", "glm47_flash_tiny", "--seq-len", "64",
                        "--grad-accum", "1"], {"train": {"seq_len": 64}}),
    "keye_vl2_30b_a3b_4l": (["--model", "keye_vl2_tiny", "--seq-len", "64"],
                            {"train": {"seq_len": 64}}),
}


def _flags(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return list(json.load(f)["train_flags"]) + CUTS[name][0] + \
            ["--workers", "1"]


@pytest.fixture(scope="module", params=sorted(CUTS))
def built(request, tmp_path_factory):
    """The runner's objects and the driver's, from the same flags, the same
    weights, the same dataset and the same loader seed."""
    from benchmark.drivers.train import Built
    from benchmark.drivers.train_tokens import TokenBuilt
    name = request.param
    out = str(tmp_path_factory.mktemp(name))
    flags = _flags(name)
    program = T.build_program(
        TrainConfig.from_args(flags + ["--output", out]))
    cfg = program.cfg

    # zeros in the shapes of an init: the lowered step reads shapes only,
    # and a compile of the flagship's init is not this test's to pay
    shape = (1, 8) if program.sequence_task else \
        (1, *program.input_size[1:], program.input_size[0])
    abstract = jax.eval_shape(lambda: init_model(
        program.model, jax.random.PRNGKey(0), shape, training=True,
        dtype=jnp.int32 if program.sequence_task else jnp.float32))

    def variables():
        return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract)

    state, shardings = T.init_state(program, None, variables=variables())
    train_step, eval_step, eval_step_ema = T.build_steps(program, shardings)
    assert eval_step is not None and eval_step_ema is None

    config = dict(CUTS[name][1], train_flags=flags)
    if program.sequence_task:
        config["vocab_size"] = program.model.vocab_rows
        theirs = TokenBuilt(SimpleNamespace(config=config), out)
        dataset = SyntheticTokenDataset(4 * program.global_batch,
                                        cfg.seq_len,
                                        program.model.vocab_rows, 7)
    else:
        theirs = Built(SimpleNamespace(config=config), out)
        c, h, w = program.input_size
        dataset = SyntheticDataset(4 * program.global_batch, (h, w, c),
                                   cfg.num_classes, 7)
    their_state = theirs.state_for(variables())
    return SimpleNamespace(name=name, program=program, state=state,
                           train_step=train_step, theirs=theirs,
                           their_state=their_state, dataset=dataset)


def _first_batches(loader, n=2):
    loader.set_epoch(0)
    out = []
    for i, batch in enumerate(loader):
        out.append(batch)
        if i + 1 == n:
            break
    loader.close()
    return out


def _same(a, b) -> bool:
    return np.array_equal(np.asarray(a.astype(jnp.float32)),
                          np.asarray(b.astype(jnp.float32)))


def test_program_record_matches_the_drivers(built):
    """The record carries what ``Built`` carries, under its names."""
    p, b = built.program, built.theirs
    assert p.mesh == b.mesh and p.n_dev == b.n_dev == 8
    assert (p.batch_axis, p.dp, p.lr, p.global_batch) == \
        (b.batch_axis, b.dp, b.lr, b.global_batch)
    assert p.global_batch == p.cfg.batch_size * 8
    if not p.sequence_task:
        assert p.input_size == b.input_size
        assert p.data_config == b.data_config
    assert type(p.model) is type(b.model)
    assert type(p.lr_scheduler) is type(b.lr_scheduler)


def test_loaders_and_lowered_step_equal_the_drivers(built):
    """``build_loaders`` with a caller's dataset and seed feeds what the
    drivers' ``loader_for`` feeds, and the step of ``build_steps`` lowers to
    the StableHLO text of the step the drivers make: the later swap inside
    the drivers is a no-op by this proof."""
    p = built.program
    seed = 1234
    ours, eval_loader = T.build_loaders(p, built.dataset, seed=seed)
    assert eval_loader is None and len(ours) == 4
    theirs = built.theirs.loader_for(built.dataset, seed, 0)[0]
    mine, their = _first_batches(ours), _first_batches(theirs)
    for (x, y), (xt, yt) in zip(mine, their):
        assert x.shape[0] == p.global_batch
        assert _same(x, xt) and _same(y, yt)
    # another seed is another stream (the argument is not ignored) ...
    other = _first_batches(T.build_loaders(p, built.dataset, seed=99)[0])
    assert not all(_same(a[0], b[0]) for a, b in zip(mine, other))
    # ... and the default is the configuration's
    default = _first_batches(T.build_loaders(p, built.dataset)[0], 1)
    at_cfg = _first_batches(
        T.build_loaders(p, built.dataset, seed=p.cfg.seed)[0], 1)
    assert _same(default[0][0], at_cfg[0][0])

    x, y = mine[0]
    rng = jax.device_put(jax.random.PRNGKey(0),
                         T.replicated_sharding(p.mesh))

    def text(step, state):
        spec = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            (state, x, y, rng))
        return step.lower(*spec).as_text()

    assert text(built.train_step, built.state) == \
        text(built.theirs.train_step, built.their_state)


def test_init_state_takes_the_callers_weights(built):
    """``init_state(variables=)`` places the caller's tree (no init, no
    rng) under the table's shardings, like the drivers' ``state_for``."""
    ours, theirs = built.state, built.their_state
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert a.sharding == b.sharding
    assert all(not np.any(np.asarray(a))
               for a in jax.tree.leaves(ours.params))


def test_telemetry_is_the_runners(built, tmp_path):
    """``build_telemetry`` hands a consumer the runner's tracker: the
    model's own FLOP count and attention-tile census, not zeros."""
    p = built.program
    loader = T.build_loaders(p, built.dataset)[0]
    try:
        telemetry, server, profiler = T.build_telemetry(
            p, built.state, loader, str(tmp_path))
    finally:
        loader.close()
    assert server is None                   # no --metrics-port
    assert telemetry.profiler is profiler   # --profile-capture's default
    snap = telemetry.snapshot()
    profiler.close()
    telemetry.close()
    gflops = snap["gauges"]["model_fwd_gflops_per_sample"]
    if p.sequence_task:
        assert gflops == 0.0                        # ROADMAP D13
        # the sparse-attention kernels run on a TPU only (the array form
        # here visits no tile); every other sequence model's kernels run
        # interpreted
        assert telemetry.attn_tiles_per_sample == \
            p.model.attn_tiles_visited(p.cfg.seq_len)
        assert (telemetry.attn_tiles_per_sample > 0) == (p.dsa_layers == 0)
    else:
        assert gflops > 0.0
        assert telemetry.attn_tiles_per_sample == 0
    # the depthwise census (PR 29): build_program counted the stages; on
    # the CPU, over the test's eight devices, every one keeps XLA's gradient
    kernel, xla = p.dw_grad_stages
    assert (snap["gauges"]["dw_grad_kernel_stages"],
            snap["gauges"]["dw_grad_xla_stages"]) == (kernel, xla)
    assert kernel == 0 and (xla == 0) == p.sequence_task
    assert "dfd_train_dw_grad_xla_stages" in telemetry.render_prometheus()
    # the causal convolutions' census (PR 31): on the CPU every Mamba layer
    # takes the array form; image models have none
    assert p.causal_conv_layers == (
        p.model.causal_conv_layers(p.cfg.seq_len)
        if hasattr(p.model, "causal_conv_layers") else (0, 0))
    assert (snap["gauges"]["causal_conv_kernel_layers"],
            snap["gauges"]["causal_conv_xla_layers"]) == p.causal_conv_layers
    assert p.causal_conv_layers[0] == 0
    # the attention backward's census (PR 33): a function of the shape alone,
    # so the CPU reads what the chip reads; image models have no such layer
    assert p.attn_bwd_layers == (
        p.model.attn_bwd_layers(p.cfg.seq_len)
        if hasattr(p.model, "attn_bwd_layers") else (0, 0))
    assert (snap["gauges"]["attn_fused_bwd_layers"],
            snap["gauges"]["attn_split_bwd_layers"]) == p.attn_bwd_layers
    # (a model of learned sparse attention has no flash layer)
    assert (p.attn_bwd_layers[0] > 0) == (p.sequence_task
                                          and p.dsa_layers == 0)
    assert p.attn_bwd_layers[1] == 0
    assert "dfd_train_attn_fused_bwd_layers" in telemetry.render_prometheus()
    # the saved-forward census: every flash layer of a sequence model
    # under its configuration's remat policy (full), none of an image model
    assert snap["gauges"]["attn_fwd_saved_layers"] == \
        p.attn_fwd_saved_layers == (sum(p.attn_bwd_layers)
                                    if p.cfg.checkpoint_policy != "none"
                                    else 0)
    assert "dfd_train_attn_fwd_saved_layers" in \
        telemetry.render_prometheus()
    # the latent-attention census: every layer of the GLM stack, no other
    assert snap["gauges"]["mla_layers"] == p.mla_layers == (
        p.model.mla_layers() if hasattr(p.model, "mla_layers") else 0)
    # the learned-sparse-attention census: every layer of the Keye stack
    assert snap["gauges"]["dsa_layers"] == p.dsa_layers == (
        p.model.dsa_layers() if hasattr(p.model, "dsa_layers") else 0)
    assert "dfd_train_dsa_layers" in telemetry.render_prometheus()
    # of those, the layers whose forward reads the selection kernel's bits:
    # none on the CPU, where the array form runs
    assert snap["gauges"]["dsa_fwd_bits_layers"] == \
        p.dsa_fwd_bits_layers == 0
    assert os.path.isfile(tmp_path / "telemetry.jsonl")


@pytest.mark.parametrize("name,saved", [
    ("phi4_mini_flash_6l", 3), ("granite4_h_micro_10l", 1),
    ("lfm2_24b_a2b_5l", 1), ("glm47_flash_5l", 5)])
def test_the_saved_forward_census_at_the_cells_size(name, saved):
    """``attn_fwd_saved_layers`` of each sequence configuration's own flags
    (full size: the census reads shapes, not arrays): every attention
    layer under the cell's ``full``, which saves the flash op's output and
    row statistics, none under ``none``."""
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        flags = json.load(f)["train_flags"]
    mesh = T.make_train_mesh(batch=1, model=1, devices=jax.devices()[:1])
    census = {policy: T.build_program(TrainConfig.from_args(
        flags + ["--checkpoint-policy", policy]),
        mesh=mesh).attn_fwd_saved_layers for policy in ("full", "none")}
    assert census == {"full": saved, "none": 0}


# ---- where a restored snapshot puts the loop -------------------------------

@pytest.mark.parametrize("meta,per_epoch,expected", [
    pytest.param({"epoch": 2, "batch_idx": 4}, 10, (2, 5),
                 id="recovery-mid-epoch"),
    pytest.param({"epoch": 2, "batch_idx": 0}, 10, (2, 1),
                 id="recovery-first-batch"),
    pytest.param({"epoch": 2, "batch_idx": 9}, 10, (3, 0),
                 id="recovery-last-batch-rolls-over"),
    pytest.param({"epoch": 0, "batch_idx": 3}, 4, (1, 0),
                 id="recovery-last-batch-of-epoch-0"),
    pytest.param({"epoch": 2}, 10, (3, 0), id="epoch-boundary"),
    pytest.param({}, 10, (0, 0), id="no-epoch-recorded"),
    pytest.param({"epoch": 1, "batch_idx": 2}, 0, (1, 3),
                 id="empty-loader-keeps-the-position"),
])
def test_resume_position(meta, per_epoch, expected):
    assert resume_position(meta, per_epoch) == expected


class _Sched:
    def step(self, epoch, metric=None):
        return 10.0 ** -epoch


@pytest.mark.parametrize("meta,rederived", [
    pytest.param({"epoch": 2, "batch_idx": 4}, False, id="mid-epoch-keeps"),
    pytest.param({"epoch": 2, "batch_idx": 9}, True, id="rolled-over"),
    pytest.param({"epoch": 2}, True, id="epoch-boundary"),
    pytest.param({}, False, id="epoch-0-keeps"),
])
def test_lr_is_rederived_at_an_epoch_boundary_only(meta, rederived):
    """One rule after ``resume_position``, for start-up and rewind alike:
    the snapshot's injected LR stays on a mid-epoch entry and is re-derived
    for the epoch entered at batch 0 (the ROLLED-OVER epoch's, not the
    snapshot's)."""
    import optax

    from deepfake_detection_tpu.train import (create_train_state,
                                              get_learning_rate)
    tx = optax.inject_hyperparams(optax.sgd)(learning_rate=0.5)
    state = create_train_state({"params": {"w": jnp.ones((2,))}}, tx)
    epoch, batch = resume_position(meta, 10)
    state = T._enter_at(state, _Sched(), epoch, batch)
    want = 10.0 ** -epoch if rederived else 0.5
    assert get_learning_rate(state) == pytest.approx(want)
    assert T._enter_at(state, None, 3, 0) is state      # no scheduler
