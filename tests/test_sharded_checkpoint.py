"""Sharded (Orbax) checkpointing: collective save, resharding restore.

The msgpack path serializes the full model on rank 0 (after a
replicate_for_save all-gather for multi-host model-parallel state);
``save_sharded_checkpoint`` instead writes each host's addressable shards
directly and restores into whatever sharding the template asks for — the
save path that scales with model-parallel size (reference torch.save has
no equivalent, utils.py:97-112).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepfake_detection_tpu.parallel import (batch_sharding,
                                             fsdp_param_specs, make_mesh)
from deepfake_detection_tpu.train import (create_train_state,
                                          make_train_step,
                                          restore_sharded_checkpoint,
                                          save_sharded_checkpoint)

def _tiny_state(mesh, fsdp=False):
    from types import SimpleNamespace

    from deepfake_detection_tpu.losses import cross_entropy
    from deepfake_detection_tpu.models import create_model, init_model
    from deepfake_detection_tpu.optim import create_optimizer

    model = create_model("mnasnet_small", num_classes=2, in_chans=3)
    variables = init_model(model, jax.random.PRNGKey(0), (2, 32, 32, 3),
                           training=True)
    if fsdp:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        specs = fsdp_param_specs(variables["params"], mesh, min_size=256)
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P))
        variables = {
            "params": jax.tree.map(jax.device_put, variables["params"],
                                   shardings),
            "batch_stats": jax.device_put(
                variables["batch_stats"],
                jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec())),
        }
    tx = create_optimizer(SimpleNamespace(
        opt="sgd", opt_eps=1e-8, momentum=0.9, weight_decay=0.0, lr=0.01))
    state = create_train_state(variables, tx)
    step = make_train_step(model, tx, cross_entropy, mesh=mesh,
                           bn_mode="global")
    return model, state, step, tx


@pytest.mark.smoke
def test_meta_json_default_converts_numpy_rejects_unknown():
    """Sharded-save meta serialization (ISSUE 1 satellite): numpy arrays
    become lists, numpy scalars become Python scalars, and any other
    unknown type raises instead of round-tripping as a garbage str()."""
    import json

    from deepfake_detection_tpu.train.checkpoint import _meta_json_default

    blob = json.dumps(
        {"arr": np.arange(3), "f": np.float32(0.5), "i": np.int64(7)},
        default=_meta_json_default)
    assert json.loads(blob) == {"arr": [0, 1, 2], "f": 0.5, "i": 7}
    with pytest.raises(TypeError, match="not\\s+JSON-serializable"):
        json.dumps({"bad": object()}, default=_meta_json_default)


class TestShardedCheckpoint:
    @pytest.mark.slow   # tier-1 budget: full FSDP save/restore sweep
    # (~16 s); test_restore_reshards_onto_new_layout and the msgpack
    # mesh-continuity tests keep the resharded-restore mechanism fast
    def test_fsdp_roundtrip_preserves_values_and_shardings(
            self, tmp_path, devices):
        mesh = make_mesh()
        _, state, step, _ = _tiny_state(mesh, fsdp=True)
        x = jax.device_put(np.random.default_rng(0).normal(
            size=(8, 32, 32, 3)).astype(np.float32), batch_sharding(mesh))
        y = jax.device_put(np.arange(8) % 2, batch_sharding(mesh))
        state, _ = step(state, x, y, jax.random.PRNGKey(1))

        path = str(tmp_path / "sharded_ckpt")
        # numpy scalars in meta must be accepted (the msgpack path's meta
        # round-trips them; the json meta converts them up front)
        save_sharded_checkpoint(path, state, {"epoch": 3,
                                              "metric": np.float32(0.75)})

        _, template, _, _ = _tiny_state(mesh, fsdp=True)
        restored, meta = restore_sharded_checkpoint(path, template)
        assert meta["epoch"] == 3
        assert meta["metric"] == pytest.approx(0.75)
        assert int(restored.step) == 1
        # the contract: values from the checkpoint, shardings from the
        # TEMPLATE (the stepped state's GSPMD-chosen layout may differ)
        sharded = 0
        for a, t, b in zip(jax.tree.leaves(state.params),
                           jax.tree.leaves(template.params),
                           jax.tree.leaves(restored.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert b.sharding.is_equivalent_to(t.sharding, t.ndim), \
                (t.sharding, b.sharding)
            sharded += not b.sharding.is_fully_replicated
        assert sharded > 0          # fsdp leaves actually stayed sharded

    def test_restore_reshards_onto_new_layout(self, tmp_path, devices):
        """Save replicated, restore into an fsdp template: the template's
        shardings win — the mesh-migration path (e.g. resume a dp run as
        dp+fsdp) with no manual re-layout."""
        mesh = make_mesh()
        _, state, _, _ = _tiny_state(mesh, fsdp=False)
        path = str(tmp_path / "ckpt_replicated")
        save_sharded_checkpoint(path, state)

        _, template, _, _ = _tiny_state(mesh, fsdp=True)
        restored, _ = restore_sharded_checkpoint(path, template)
        t_leaves = jax.tree.leaves(template.params)
        r_leaves = jax.tree.leaves(restored.params)
        s_leaves = jax.tree.leaves(state.params)
        assert any(not t.sharding.is_fully_replicated for t in t_leaves)
        for t, r, s in zip(t_leaves, r_leaves, s_leaves):
            assert r.sharding.is_equivalent_to(t.sharding, t.ndim)
            np.testing.assert_array_equal(np.asarray(r), np.asarray(s))

    @pytest.mark.slow   # tier-1 budget: cross-optimizer resume policy
    # drive (~8 s); the load_opt=False mechanism stays fast via
    # test_train::TestCheckpointing::test_no_resume_opt
    def test_no_resume_opt_under_different_optimizer(self, tmp_path,
                                                     devices):
        """load_opt=False must not read or structure-match the saved
        opt_state: resume SGD-with-momentum weights under plain Adam."""
        from types import SimpleNamespace

        from deepfake_detection_tpu.optim import create_optimizer

        mesh = make_mesh()
        _, state, step, _ = _tiny_state(mesh)
        x = jax.device_put(np.ones((8, 32, 32, 3), np.float32),
                           batch_sharding(mesh))
        y = jax.device_put(np.zeros(8, np.int64), batch_sharding(mesh))
        state, _ = step(state, x, y, jax.random.PRNGKey(0))
        path = str(tmp_path / "ckpt")
        save_sharded_checkpoint(path, state)

        tx2 = create_optimizer(SimpleNamespace(
            opt="adam", opt_eps=1e-8, momentum=0.9, weight_decay=0.0,
            lr=1e-3))
        template = create_train_state(
            jax.tree.map(jnp.copy, state.variables), tx2)
        restored, _ = restore_sharded_checkpoint(path, template,
                                                 load_opt=False)
        # params restored, optimizer state fresh (step back to 0)
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(restored.params)[0]),
            np.asarray(jax.tree.leaves(state.params)[0]))
        assert int(restored.step) == 0

    @pytest.mark.slow
    def test_runner_ckpt_sharded_train_and_resume(self, tmp_path, devices):
        """--ckpt-sharded end-to-end: train writes checkpoint DIRECTORIES
        + a model_best.json pointer; --resume <dir> restores through the
        collective sharded path."""
        import os

        from deepfake_detection_tpu.runners.train import launch_main

        args = [
            "--dataset", "synthetic", "--model", "mnasnet_small",
            "--model-version", "", "--input-size-v2", "3,32,32",
            "--batch-size", "1", "--epochs", "1",
            "--opt", "sgd", "--lr", "0.01", "--sched", "step",
            "--log-interval", "10", "--workers", "1",
            "--compute-dtype", "float32", "--ckpt-sharded",
            "--output", str(tmp_path / "o1")]
        out = launch_main(args)
        assert out["best_metric"] is not None
        run = os.path.join(tmp_path, "o1", os.listdir(tmp_path / "o1")[0])
        ckpt = os.path.join(run, "checkpoint-0")
        assert os.path.isdir(ckpt)                      # a directory
        assert os.path.isfile(os.path.join(ckpt, "dfd_meta.json"))
        import json
        best = json.load(open(os.path.join(run, "model_best.json")))
        assert best["checkpoint"] == ckpt
        out = launch_main(args[:-1] + [str(tmp_path / "o2"),
                                       "--resume", ckpt, "--epochs", "2"])
        assert out["best_metric"] is not None

    @pytest.mark.slow   # tier-1 budget: full train-run fixture (~16 s);
    # EMA-stream preference is also pinned fast by the ema helpers in
    # test_train/test_utils and restore_reshards stays fast above
    def test_load_for_eval_prefers_ema(self, tmp_path, devices):
        """Serving path: load_sharded_for_eval pulls the EMA stream from a
        sharded TRAIN checkpoint (the reference ships its released model
        from EMA), falling back to raw params without one."""
        from types import SimpleNamespace

        import numpy as np

        from deepfake_detection_tpu.losses import cross_entropy
        from deepfake_detection_tpu.models import create_model, init_model
        from deepfake_detection_tpu.optim import create_optimizer
        from deepfake_detection_tpu.train import make_train_step
        from deepfake_detection_tpu.train.checkpoint import \
            load_sharded_for_eval

        mesh = make_mesh()
        model = create_model("mnasnet_small", num_classes=2, in_chans=3)
        variables = init_model(model, jax.random.PRNGKey(0), (2, 32, 32, 3),
                               training=True)
        tx = create_optimizer(SimpleNamespace(
            opt="sgd", opt_eps=1e-8, momentum=0.0, weight_decay=0.0,
            lr=0.05))
        state = create_train_state(
            jax.tree.map(jnp.copy, variables), tx, with_ema=True)
        step = make_train_step(model, tx, cross_entropy, mesh=mesh,
                               bn_mode="global", ema_decay=0.5)
        x = jax.device_put(np.ones((8, 32, 32, 3), np.float32),
                           batch_sharding(mesh))
        y = jax.device_put(np.zeros(8, np.int64), batch_sharding(mesh))
        state, _ = step(state, x, y, jax.random.PRNGKey(1))
        path = str(tmp_path / "train_ckpt")
        save_sharded_checkpoint(path, state)

        out = load_sharded_for_eval(path, variables, use_ema=True)
        # EMA(decay=.5) after one step sits strictly between init and the
        # updated params wherever they moved
        ema_leaf = np.asarray(jax.tree.leaves(out["params"])[0])
        par_leaf = np.asarray(jax.tree.leaves(state.params)[0])
        np.testing.assert_array_equal(
            ema_leaf, np.asarray(jax.tree.leaves(state.ema["params"])[0]))
        assert not np.array_equal(ema_leaf, par_leaf)
        out2 = load_sharded_for_eval(path, variables, use_ema=False)
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(out2["params"])[0]), par_leaf)
        # a model can consume the result directly
        logits = model.apply(out, jnp.zeros((1, 32, 32, 3)), training=False)
        assert logits.shape == (1, 2)
        # EMA-less checkpoint (ema=None in the TrainState): use_ema=True
        # must FALL BACK to raw params, not crash on the None placeholder
        state_no_ema = create_train_state(
            jax.tree.map(jnp.copy, variables), tx, with_ema=False)
        path2 = str(tmp_path / "train_ckpt_no_ema")
        save_sharded_checkpoint(path2, state_no_ema)
        out3 = load_sharded_for_eval(path2, variables, use_ema=True)
        assert "params" in out3 and "batch_stats" in out3

    def test_qkv_layout_guard(self, tmp_path, devices):
        """A sharded fused-qkv checkpoint without the head-major marker
        must be rejected, like the msgpack path (models/helpers.py)."""
        import flax.struct

        @flax.struct.dataclass
        class Fake:
            params: dict

        state = Fake(params={"blocks_0": {"attn": {"qkv": {
            "kernel": jnp.zeros((8, 24))}}}})
        path = str(tmp_path / "vit_ckpt")
        save_sharded_checkpoint(path, state)           # meta gets marker
        restore_sharded_checkpoint(path, state)        # marker honored
        # simulate a foreign/legacy checkpoint: strip the marker
        import json
        import os
        with open(os.path.join(path, "dfd_meta.json"), "w") as f:
            json.dump({}, f)
        with pytest.raises(ValueError, match="qkv_layout"):
            restore_sharded_checkpoint(path, state)
        # and an interrupted save: no meta marker at all
        os.remove(os.path.join(path, "dfd_meta.json"))
        with pytest.raises(FileNotFoundError, match="interrupted"):
            restore_sharded_checkpoint(path, state)


class TestMsgpackMeshContinuity:
    """ISSUE 12 satellite: the msgpack checkpoint format is mesh-portable.

    ``restore_resharded`` re-lays host arrays onto the TEMPLATE's
    sharding-table annotations, so a checkpoint written on a (1,1) mesh
    restores onto an (8,1) layout — including FSDP resharding — and vice
    versa, with values bit-identical either way.  The PR 3 resume ladder
    routes through this exact function (train/checkpoint.py:restore_any).
    """

    def _unified_state(self, devices, n_batch, fsdp=False):
        from types import SimpleNamespace
        from deepfake_detection_tpu.models import create_model, init_model
        from deepfake_detection_tpu.optim import create_optimizer
        from deepfake_detection_tpu.parallel import (make_train_mesh,
                                                     place_train_state,
                                                     train_state_shardings)
        model = create_model("mnasnet_small", num_classes=2, in_chans=3)
        variables = init_model(model, jax.random.PRNGKey(0),
                               (2, 32, 32, 3), training=True)
        tx = create_optimizer(SimpleNamespace(
            opt="sgd", opt_eps=1e-8, momentum=0.9, weight_decay=0.0,
            lr=0.01), inject=True)
        state = create_train_state(variables, tx, donate=False)
        mesh = make_train_mesh(batch=n_batch, model=1,
                               devices=devices[:n_batch])
        sh = train_state_shardings(state, mesh, fsdp=fsdp)
        return place_train_state(state, sh), sh

    def test_one_chip_checkpoint_restores_onto_eight_way_mesh(
            self, tmp_path, devices):
        from jax.sharding import PartitionSpec as P
        from deepfake_detection_tpu.train import (restore_resharded,
                                                  save_checkpoint_file)
        small, _ = self._unified_state(devices, 1)
        path = str(tmp_path / "one_chip.ckpt")
        save_checkpoint_file(path, small, {"epoch": 4})
        template, sh = self._unified_state(devices, 8, fsdp=True)
        restored, meta = restore_resharded(path, template)
        assert meta["epoch"] == 4
        resharded = 0
        for got, want, orig in zip(jax.tree.leaves(restored),
                                   jax.tree.leaves(sh),
                                   jax.tree.leaves(small)):
            assert got.sharding == want
            if want.spec != P():
                resharded += 1
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(orig))
        assert resharded > 0, "template had no FSDP-sharded leaf"

    @pytest.mark.slow   # tier-1 budget: reverse direction of the mesh-
    # continuity pair (~4 s); one_chip→eight_way stays fast and pins the
    # same restore_resharded path
    def test_eight_way_checkpoint_restores_onto_one_chip(
            self, tmp_path, devices):
        from deepfake_detection_tpu.train import (restore_resharded,
                                                  save_checkpoint_file)
        big, _ = self._unified_state(devices, 8, fsdp=True)
        path = str(tmp_path / "pod.ckpt")
        save_checkpoint_file(path, big, {"epoch": 7})
        template, sh = self._unified_state(devices, 1)
        restored, meta = restore_resharded(path, template)
        assert meta["epoch"] == 7
        for got, want, orig in zip(jax.tree.leaves(restored),
                                   jax.tree.leaves(sh),
                                   jax.tree.leaves(big)):
            assert got.sharding == want
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(orig))

    def test_restored_leaves_own_their_bytes(self, tmp_path, devices):
        """The DFD002 donation-aliasing discipline survives the move into
        train/checkpoint.py: no restored leaf may be a zero-copy view of
        host memory (donating such an alias is the PR 2 SIGSEGV class)."""
        from deepfake_detection_tpu.train import (restore_resharded,
                                                  save_checkpoint_file)
        state, _ = self._unified_state(devices, 8)
        path = str(tmp_path / "own.ckpt")
        save_checkpoint_file(path, state, {})
        template, _ = self._unified_state(devices, 8)
        restored, _ = restore_resharded(path, template)
        for leaf in jax.tree.leaves(restored):
            assert isinstance(leaf, jax.Array), type(leaf)
