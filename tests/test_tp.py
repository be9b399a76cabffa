"""Tensor parallelism over a 2-D (data × model) mesh for the transformers.

GSPMD does the partitioning: we only annotate param shardings, jit the
unchanged model, and check numerics against the replicated run.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepfake_detection_tpu.models import create_model, init_model
from deepfake_detection_tpu.parallel import (batch_sharding, shard_batch,
                                             transformer_tp_sharding,
                                             transformer_tp_specs)


@pytest.fixture()
def mesh2d(devices):
    return Mesh(np.asarray(devices).reshape(2, 4), ("data", "model"))


def test_specs_follow_megatron_pairing():
    m = create_model("vit_tiny_patch16_224", num_classes=2)
    v = init_model(m, jax.random.PRNGKey(0), (1, 64, 64, 3))
    specs = transformer_tp_specs(v["params"], axis="model", axis_size=4)
    blk = specs["blocks_0"]
    assert blk["attn"]["qkv"]["kernel"] == P(None, "model")
    assert blk["attn"]["qkv"]["bias"] == P("model")
    assert blk["attn"]["proj"]["kernel"] == P("model", None)
    assert blk["attn"]["proj"]["bias"] == P()
    assert blk["mlp_fc1"]["kernel"] == P(None, "model")
    assert blk["mlp_fc2"]["kernel"] == P("model", None)
    assert specs["patch_embed"]["kernel"] == P()      # replicated
    assert specs["norm"]["scale"] == P()


@pytest.mark.parametrize("name", ["vit_tiny_patch16_224",
                                  "timesformer_tiny_patch16_224"])
def test_tp_forward_matches_replicated(mesh2d, name):
    in_chans = 12 if name.startswith("timesformer") else 3
    m = create_model(name, num_classes=2, in_chans=in_chans)
    v = init_model(m, jax.random.PRNGKey(0), (2, 64, 64, in_chans))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64, in_chans))
    ref = m.apply(v, x, training=False)

    shardings = transformer_tp_sharding(v["params"], mesh2d, axis="model")
    params_tp = jax.tree.map(jax.device_put, v["params"], shardings)
    x_tp = jax.device_put(x, batch_sharding(mesh2d, "data"))
    out = jax.jit(lambda p, x: m.apply({"params": p}, x,
                                       training=False))(params_tp, x_tp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_tp_train_step(mesh2d):
    """dp×tp train step: batch on 'data', heads/hidden on 'model'; GSPMD
    keeps the optimizer update sharded like the params."""
    from deepfake_detection_tpu.losses import cross_entropy
    from deepfake_detection_tpu.optim import create_optimizer
    from deepfake_detection_tpu.train import (create_train_state,
                                              make_train_step)
    m = create_model("vit_tiny_patch16_224", num_classes=2)
    v = init_model(m, jax.random.PRNGKey(0), (2, 64, 64, 3))
    shardings = transformer_tp_sharding(v["params"], mesh2d, axis="model")
    v = {"params": jax.tree.map(jax.device_put, v["params"], shardings)}
    cfg = SimpleNamespace(opt="adamw", opt_eps=1e-8, momentum=0.9,
                          weight_decay=1e-5, lr=1e-4)
    tx = create_optimizer(cfg)
    state = create_train_state(v, tx)
    step = make_train_step(m, tx, cross_entropy, mesh=None,
                           bn_mode="global")
    x = jax.device_put(
        np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                     (4, 64, 64, 3))),
        batch_sharding(mesh2d, "data"))
    y = jax.device_put(np.arange(4) % 2, batch_sharding(mesh2d, "data"))
    state, metrics = step(state, x, y, jax.random.PRNGKey(2))
    assert np.isfinite(float(metrics["loss"]))
    # params stay TP-sharded after the update (no silent re-replication)
    k = state.params["blocks_0"]["attn"]["qkv"]["kernel"]
    assert "model" in str(k.sharding.spec)


_CLI_DRIVER = """
import json, os, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
if cache:
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from deepfake_detection_tpu.runners.train import launch_main
out = launch_main(sys.argv[1:])
print("RESULT " + json.dumps({"best_metric": out["best_metric"]}))
"""


def _launch_cli(args):
    """Run the train CLI end-to-end in a FRESH interpreter.

    A fresh interpreter IS the artifact a CLI test should exercise — and
    process isolation means a native crash in the runner (the class of
    bug that donated-alias resume used to hit, see
    train/checkpoint.py:restore_resharded) can at worst fail this one test instead of
    killing the whole pytest process and every test after it."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(jax.config.jax_compilation_cache_dir or "")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", _CLI_DRIVER, *args],
                          cwd=repo, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, \
        f"CLI run failed rc={proc.returncode}\n{proc.stdout[-2000:]}\n" \
        f"{proc.stderr[-2000:]}"
    import json
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow   # tier-1 budget: fresh-interpreter CLI phases (~33s)
def test_tp_cli_e2e(tmp_path, devices):
    """--tp-size from the CLI: dp(2)xtp(4) synthetic smoke train."""
    out = _launch_cli([
        "--dataset", "synthetic", "--model", "vit_tiny_patch16_224",
        "--model-version", "", "--input-size-v2", "3,32,32",
        "--batch-size", "1", "--epochs", "1", "--opt", "adamw",
        "--lr", "1e-3", "--sched", "step", "--log-interval", "4",
        "--workers", "1", "--compute-dtype", "float32", "--tp-size", "4",
        "--output", str(tmp_path / "out")])
    assert out["best_metric"] is not None
    # resume re-applies the TP layout (restore rebuilds host arrays)
    run = next((tmp_path / "out").iterdir())
    out2 = _launch_cli([
        "--dataset", "synthetic", "--model", "vit_tiny_patch16_224",
        "--model-version", "", "--input-size-v2", "3,32,32",
        "--batch-size", "1", "--epochs", "2", "--opt", "adamw",
        "--lr", "1e-3", "--sched", "step", "--log-interval", "4",
        "--workers", "1", "--compute-dtype", "float32", "--tp-size", "4",
        "--resume", str(run / "model_best.ckpt"),
        "--output", str(tmp_path / "out2")])
    assert out2["best_metric"] is not None
