"""End-to-end injected-fault recovery (the chaos tier).

Every recovery path of train/resilience.py is exercised here by a REAL
fault injected into a REAL training run (fresh-interpreter CLI subprocess,
the test_tp idiom — a native crash can at worst fail one test), against
the exit-code contract:

* SIGTERM mid-epoch → exit 75 with a synchronous recovery snapshot →
  ``--auto-resume`` relaunch → final params BIT-IDENTICAL to an
  uninterrupted run (the hard criterion: resume is exact, not
  epoch-rounded).
* a poisoned-gradient burst → device-side skips, then a rewind to the
  last recovery snapshot → the run completes by itself, params finite and
  (because the rewind replays the poisoned span clean) bit-identical.
* a wedged loader → stall-watchdog abort with exit 85 and a stack dump.
* a torn recovery file → ``--auto-resume`` falls back to the previous
  snapshot instead of crashing, and still reproduces the exact stream.

Synthetic dataset, CPU, single virtual device — seconds-scale per run
with a warm jax compilation cache.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.chaos

EXIT_PREEMPTED = 75
EXIT_WATCHDOG = 85

_CLI_DRIVER = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
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

# 16 synthetic samples / batch 2 → 8 updates per epoch; RandomErasing ON so
# bit-identity also proves the device-prologue key stream fast-forwards
_BASE = ["--dataset", "synthetic", "--model", "vit_tiny_patch16_224",
         "--model-version", "", "--input-size-v2", "3,32,32",
         "--batch-size", "2", "--epochs", "2", "--opt", "adamw",
         "--lr", "1e-3", "--sched", "step", "--log-interval", "2",
         "--workers", "1", "--compute-dtype", "float32",
         "--reprob", "0.25", "--seed", "42"]


def _launch(args, chaos="", timeout=600):
    """Train-CLI run in a fresh interpreter; returns CompletedProcess."""
    env = dict(os.environ)
    env.pop("DFD_CHAOS", None)
    if chaos:
        env["DFD_CHAOS"] = chaos
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        jax.config.jax_compilation_cache_dir or "")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run([sys.executable, "-c", _CLI_DRIVER, *args],
                          cwd=repo, env=env, capture_output=True,
                          text=True, timeout=timeout)


def _state_of(ckpt_path):
    from deepfake_detection_tpu.train import load_checkpoint_file
    sd, meta = load_checkpoint_file(str(ckpt_path))
    return sd


def _assert_states_identical(a, b, context):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                      err_msg=context)


@pytest.fixture(scope="module")
def uninterrupted(tmp_path_factory):
    """The reference run every fault scenario must reproduce exactly."""
    out = tmp_path_factory.mktemp("chaos") / "ref"
    r = _launch(_BASE + ["--experiment", "ref", "--output", str(out)])
    assert r.returncode == 0, \
        f"reference run failed rc={r.returncode}\n{r.stdout[-2000:]}\n" \
        f"{r.stderr[-2000:]}"
    ckpt = out / "ref" / "checkpoint-1.ckpt"
    assert ckpt.exists()
    return ckpt


def test_sigterm_preempts_then_bit_identical_resume(tmp_path,
                                                    uninterrupted):
    out = tmp_path / "out"
    args = _BASE + ["--experiment", "run", "--output", str(out),
                    "--auto-resume"]
    # update 11 completes at epoch 1, batch 2: a MID-epoch kill, the case
    # epoch-granular restarts lose hours on
    r = _launch(args, chaos="sigterm@11")
    assert r.returncode == EXIT_PREEMPTED, \
        f"rc={r.returncode}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    run_dir = out / "run"
    assert (run_dir / "recovery-1-2.ckpt").exists(), \
        os.listdir(str(run_dir))

    r2 = _launch(args)                        # fault cleared: relaunch
    assert r2.returncode == 0, \
        f"rc={r2.returncode}\n{r2.stdout[-2000:]}\n{r2.stderr[-2000:]}"
    assert "Auto-resumed" in r2.stderr or "Auto-resumed" in r2.stdout
    _assert_states_identical(
        _state_of(uninterrupted), _state_of(run_dir / "checkpoint-1.ckpt"),
        "preempt+auto-resume diverged from the uninterrupted run")


def _one_epoch(args):
    """Same config, --epochs 1 (epoch 0's trajectory is identical, so the
    shared reference run's checkpoint-0 is still the exact oracle)."""
    i = args.index("--epochs")
    return args[:i + 1] + ["1"] + args[i + 2:]


def test_nanbatch_burst_skips_then_rewinds(tmp_path, uninterrupted):
    out = tmp_path / "out"
    # updates 4,5,6 poisoned; guard (default policy) skips each, and the
    # 3rd consecutive bad step rewinds to recovery-0-3 — from where the
    # burst replays CLEAN (chaos fires once per step), so the run heals to
    # the exact uninterrupted trajectory without restarting
    r = _launch(_one_epoch(_BASE) + ["--experiment", "run",
                                     "--output", str(out),
                                     "--recovery-interval", "4"],
                chaos="nanbatch@4x3")
    log = r.stdout + r.stderr
    assert r.returncode == 0, f"rc={r.returncode}\n{log[-3000:]}"
    assert "non-finite training step" in log
    assert "rewinding to the last recovery snapshot" in log
    sd = _state_of(out / "run" / "checkpoint-0.ckpt")
    for leaf in jax.tree.leaves(sd["params"]):
        assert np.isfinite(np.asarray(leaf)).all()
    _assert_states_identical(
        _state_of(uninterrupted.parent / "checkpoint-0.ckpt"), sd,
        "skip+rewind diverged from the uninterrupted run")


@pytest.mark.slow   # tier-1 budget: two subprocess CLI runs (~50s); the
# thread-transport variant above keeps the resume path in the fast tier
def test_sigterm_resume_bit_identical_on_shm_transport(tmp_path,
                                                       uninterrupted):
    """ISSUE 12 satellite: SIGTERM-kill → --auto-resume bit-continuity
    holds under the unified mesh step on the SHM loader transport too.

    The oracle is the shared THREAD-transport reference run: shm batches
    are bit-identical to thread batches by construction (PR 1, pinned in
    test_shm_loader), so a bit-identical resume on shm must also land
    exactly on the thread run's final params — this doubles as a
    cross-transport check of that invariant under the mesh step."""
    out = tmp_path / "out"
    args = _BASE + ["--experiment", "run", "--output", str(out),
                    "--auto-resume", "--loader-backend", "shm"]
    r = _launch(args, chaos="sigterm@11")
    assert r.returncode == EXIT_PREEMPTED, \
        f"rc={r.returncode}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    run_dir = out / "run"
    assert (run_dir / "recovery-1-2.ckpt").exists(), \
        os.listdir(str(run_dir))

    r2 = _launch(args)                        # fault cleared: relaunch
    assert r2.returncode == 0, \
        f"rc={r2.returncode}\n{r2.stdout[-2000:]}\n{r2.stderr[-2000:]}"
    assert "Auto-resumed" in r2.stderr or "Auto-resumed" in r2.stdout
    _assert_states_identical(
        _state_of(uninterrupted), _state_of(run_dir / "checkpoint-1.ckpt"),
        "shm-transport preempt+auto-resume diverged from the "
        "uninterrupted thread-transport run")


@pytest.mark.slow   # tier-1 budget: subprocess CLI run (~25s);
# the sigterm + nanbatch tests keep the core recovery paths fast
def test_loader_stall_trips_watchdog(tmp_path):
    out = tmp_path / "out"
    r = _launch(_one_epoch(_BASE) + ["--experiment", "run",
                                     "--output", str(out),
                                     "--auto-resume",
                                     "--watchdog-timeout", "10"],
                chaos="stall_loader@3:600", timeout=240)
    assert r.returncode == EXIT_WATCHDOG, \
        f"rc={r.returncode}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    assert "stall watchdog" in r.stderr
    assert "Thread" in r.stderr               # the all-threads stack dump


@pytest.mark.slow   # tier-1 budget: two subprocess CLI runs (~42s)
def test_truncated_recovery_falls_back_to_previous(tmp_path,
                                                   uninterrupted):
    out = tmp_path / "out"
    args = _one_epoch(_BASE) + ["--experiment", "run", "--output", str(out),
                                "--auto-resume", "--recovery-interval", "4"]
    r = _launch(args)
    (tmp_path / "launch1.log").write_text(r.stdout + "\n==\n" + r.stderr)
    assert r.returncode == 0, f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    run_dir = out / "run"
    newest = run_dir / "recovery-0-7.ckpt"
    older = run_dir / "recovery-0-3.ckpt"
    assert newest.exists() and older.exists()
    size = os.path.getsize(newest)
    with open(newest, "r+b") as f:            # tear the newest snapshot
        f.truncate(size // 2)

    r2 = _launch(args)
    log = r2.stdout + r2.stderr
    assert r2.returncode == 0, f"rc={r2.returncode}\n{log[-3000:]}"
    assert "skipping unusable checkpoint" in log
    assert "recovery-0-3" in log              # the fallback it used
    # resumed at epoch 0 batch 4 from the OLDER snapshot and still landed
    # exactly on the uninterrupted trajectory
    _assert_states_identical(
        _state_of(uninterrupted.parent / "checkpoint-0.ckpt"),
        _state_of(run_dir / "checkpoint-0.ckpt"),
        "corrupt-fallback resume diverged from the uninterrupted run")
