"""Live 2-process jax.distributed tests (VERDICT r3 item 5).

Spawns two real OS processes that rendezvous through
``jax.distributed.initialize`` (via the runner's ``--json-file`` cluster
path — the reference's NCCL file rendezvous analog, train.py:279-282), each
with 4 virtual CPU devices, and train+validate end-to-end over the
resulting 8-device global mesh.

Covers the paths that single-process tests cannot: ClusterConfig →
``initialize_distributed`` rank assembly, per-process batch slicing
(``local_batch = global // process_count``), the device prologue building
global arrays from process-local shards, validate()'s end-of-epoch
``process_allgather``, and (second test) tensor parallelism across
processes — a (data, model) mesh whose 'model' collectives span the
process boundary.  Passing requires both processes to return *identical*
eval metrics — which can only happen if the eval gather really assembled
the global score set (each process only evaluates its own sampler shard).
"""

import json
import os
import socket
import subprocess
import sys

import pytest

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

_WORKER = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
from deepfake_detection_tpu.runners.train import launch_main
metrics = launch_main(sys.argv[1:])
print("METRICS_JSON=" + json.dumps(metrics), flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_two_process(tmp_path, extra_args, timeout=1200, tag="",
                     shared_output=False):
    cluster = {
        "world_size": 2,
        "coordinator_address": f"localhost:{_free_port()}",
        "servers": [{"name": socket.gethostname(), "gpus": "",
                     "local_size": 2, "start_rank": 0}],
    }
    cluster_json = tmp_path / f"cluster{tag}.json"
    cluster_json.write_text(json.dumps(cluster))

    env = dict(os.environ)
    env.update(
        # workers must be pure local CPU
        PYTHONPATH=_REPO,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_COMPILATION_CACHE_DIR=os.path.join(_REPO, ".jax_cache"),
    )

    args = ["--dataset", "synthetic", "--batch-size", "1", "--epochs", "1",
            "--log-interval", "1", "--workers", "0",
            "--json-file", str(cluster_json), *extra_args]
    def _output(i: int) -> str:
        # collective (sharded) savers need every rank on ONE directory;
        # the rank-0-only saver gets per-rank dirs so the tests can
        # assert only rank 0 wrote
        return str(tmp_path / (f"out{tag}" if shared_output
                               else f"out{tag}{i}"))

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, *args,
             "--local-rank", str(i), "--output", _output(i)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=_REPO)
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()

    metrics = []
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {i} failed:\n{out[-4000:]}"
        lines = [ln for ln in out.splitlines()
                 if ln.startswith("METRICS_JSON=")]
        assert lines, f"rank {i} printed no metrics:\n{out[-2000:]}"
        metrics.append(json.loads(lines[-1][len("METRICS_JSON="):]))
    return metrics


def _assert_lockstep(metrics):
    m0, m1 = metrics
    # identical final metrics across ranks ⇔ train steps stayed in lockstep
    # and the eval gather assembled the same global score set on both
    # (best_metric/best_epoch are saver-derived and the saver is rank-0-only)
    assert m0.keys() == m1.keys() and "auc" in m0, (m0, m1)
    for k in ("loss", "prec1", "auc"):
        assert m0[k] == pytest.approx(m1[k], abs=1e-6), (k, m0[k], m1[k])
    assert 0.0 <= m0["auc"] <= 1.0
    assert m0["best_metric"] is not None


@pytest.mark.slow
def test_two_process_train_and_validate(tmp_path):
    metrics = _run_two_process(tmp_path, [
        "--model", "mnasnet_small", "--model-version", "",
        "--input-size-v2", "3,32,32"])
    _assert_lockstep(metrics)
    # rank 0 (and only rank 0) wrote checkpoints
    ckpts0 = [f for _, _, fs in os.walk(tmp_path / "out0") for f in fs
              if f.endswith(".ckpt")]
    ckpts1 = [f for _, _, fs in os.walk(tmp_path / "out1") for f in fs
              if f.endswith(".ckpt")]
    assert ckpts0 and not ckpts1, (ckpts0, ckpts1)


@pytest.mark.slow
def test_two_process_tensor_parallel_and_resume(tmp_path):
    """dp×tp across the process boundary: a (4, 2) (data, model) mesh over
    2 processes — the 'model'-axis collectives GSPMD inserts for the
    Megatron-paired ViT shardings (parallel/tp.py) span processes, which
    no single-process test can exercise.  Then RESUME from the rank-0
    checkpoint with a second 2-process run: covers the multi-host
    checkpoint round-trip (replicate_for_save gather on write, host
    arrays re-laid onto cross-process TP shardings on read)."""
    args = ["--model", "vit_tiny_patch16_224", "--model-version", "",
            "--input-size-v2", "3,32,32", "--tp-size", "2"]
    metrics = _run_two_process(tmp_path, args)
    _assert_lockstep(metrics)

    ckpts = sorted(
        p for p in (tmp_path / "out0").rglob("checkpoint-*.ckpt"))
    assert ckpts, list((tmp_path / "out0").rglob("*"))
    metrics2 = _run_two_process(
        tmp_path, args + ["--resume", str(ckpts[-1]), "--epochs", "2"],
        tag="r")
    _assert_lockstep(metrics2)
    # the resumed run really continued from epoch 1
    assert metrics2[0]["best_epoch"] == 1, metrics2[0]


@pytest.mark.slow
def test_two_process_sharded_checkpoint(tmp_path):
    """--ckpt-sharded across a REAL process boundary: a (4, 2) dp×tp mesh
    whose model-sharded state each process saves its OWN shards of
    (collective Orbax save, no replicate_for_save gather), then a second
    2-process run resumes from the checkpoint directory with the
    collective resharding restore.  Covers what the single-process mesh
    tests cannot: per-host shard writes, the cross-process completeness
    barrier, and a restore whose template shards span processes."""
    args = ["--model", "vit_tiny_patch16_224", "--model-version", "",
            "--input-size-v2", "3,32,32", "--tp-size", "2",
            "--ckpt-sharded", "--experiment", "shard"]
    metrics = _run_two_process(tmp_path, args, shared_output=True)
    _assert_lockstep(metrics)
    run_dir = tmp_path / "out" / "shard"
    ckpt = run_dir / "checkpoint-0"
    assert ckpt.is_dir(), list(run_dir.iterdir())
    assert (ckpt / "dfd_meta.json").is_file()
    assert json.loads(
        (run_dir / "model_best.json").read_text())["checkpoint"] \
        == str(ckpt)

    metrics2 = _run_two_process(
        tmp_path, args + ["--resume", str(ckpt), "--epochs", "2"],
        tag="r", shared_output=True)
    _assert_lockstep(metrics2)
    assert metrics2[0]["best_epoch"] == 1, metrics2[0]
