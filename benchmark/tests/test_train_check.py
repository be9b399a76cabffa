"""The training comparison at a size a test run can hold (EfficientNet-B0,
12x64x64, batch 4, on the CPU, float32 so that rounding does not blur it).

* a sound run of the rest of a run (``drivers/train.run`` without its look
  for a chip) comes out correct;
* the control -- the reference put in the program's place one precision
  lower (bfloat16 under this float32 configuration) -- comes out not correct;
* each planted fault of the timed path comes out not correct: a step that
  returns its state unchanged; half of the batch left out, the mean taken
  over the rest; a batch blended with the wrong partner rows where the host
  loader produces it.  (One chip: there is no exchange to leave out.)
"""
import os
import time

import pytest

from benchmark.drivers import train as D
from benchmark.lib import manifest as M

MAN = M.load_json(os.path.join(M.BENCH, "tests", "tiny",
                               "BENCHMARK.tiny.json"))


def _run(fault=None, seed=20260930, trace=False):
    cell = M.Cell("train_tiny_f32", MAN)
    return D.run(cell, seed, 2.0, trace, time.time(), need_chip=False,
                 fault=fault)


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_sound_run_is_correct_and_prints_each_number_beside_its_limit(sound):
    assert sound["correct"] is True
    for k in ("loss_gap", "grad1_gap", "delta_gap", "batch_gap",
              "target_gap"):
        c = sound["compared"][k]
        assert 0 <= c["value"] <= c["limit"]
    assert set(sound["compared"]) == set(
        M.Cell("train_tiny_f32", MAN).config["reference"]["limits"])
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_clips_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "blend_not_mirrored"])
def test_planted_fault_is_not_correct(fault):
    res = _run(fault=fault)
    assert res["correct"] is False
    over = [k for k, c in res["compared"].items()
            if not c["value"] <= c["limit"]]
    assert over, res["compared"]
    if fault == "blend_not_mirrored":
        assert "batch_gap" in over


def test_the_recipe_without_mixup_rebuilds_its_batches_from_the_pool():
    cell = M.Cell("train_tiny_f32_nomix", MAN)
    res = D.run(cell, 11, 2.0, False, time.time(), need_chip=False)
    assert res["correct"] is True
    assert res["compared"]["batch_gap"]["value"] == 0


def test_control_one_precision_lower_is_not_correct(sound):
    """The control needs no window: the reference in bfloat16 against the
    reference, on the feed and weights of a sound run's first steps."""
    import jax
    cell = M.Cell("train_tiny_f32", MAN)
    built_batch = 4
    dataset, variables, spec = D.make_inputs(cell, 7, built_batch)
    host = jax.device_get(variables)
    rng_batches = []
    import numpy as np
    g = np.random.default_rng(7)
    for i in range(D.CHECK_STEPS):
        imgs = np.stack([dataset.pool[(i * 4 + j) % len(dataset.pool)]
                         for j in range(4)])
        lam = g.uniform(0.3, 0.7, (4, 1)).astype(np.float32)
        rng_batches.append((imgs, np.concatenate([lam, 1 - lam], 1)))
    ref = D.reference_first_steps(cell.config, spec, host["params"],
                                  host["batch_stats"], rng_batches, 7)
    ctl = D.reference_first_steps(cell.config, spec, host["params"],
                                  host["batch_stats"], rng_batches, 7,
                                  quant=cell.config["reference"]["control"])
    ok, compared = D.judge(D.compare(ctl, ref),
                           cell.config["reference"]["limits"])
    assert ok is False, compared
    same, _ = D.judge(D.compare(ref, ref), cell.config["reference"]["limits"])
    assert same is True
