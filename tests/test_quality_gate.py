"""Quality-gate machinery end-to-end (VERDICT r3 item 6).

The north-star gate is "DeeperForensics AUC ≥ the released GPU checkpoint"
(reference README.md:35-40).  The released ``model_half.pth.tar``
lives behind BaiduYun and the dataset is unavailable here, so this proves the
*machinery* instead: train the REFERENCE torch stack (vendored at
/root/reference, loaded standalone) on deterministic synthetic 4-frame data
until it actually learns, convert the trained checkpoint with
``tools/convert_torch_checkpoint.py``, and assert the converted flax model
reproduces the torch model's logits and AUC on a held-out split.

This retires the "converter is parity-tested at init but has never carried a
*trained* artifact" risk: a trained checkpoint exercises moved BN running
stats, non-symmetric weights, and a real decision boundary.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
from convert_torch_checkpoint import convert_state_dict  # noqa: E402

from test_convert import _load_reference_efficientnet  # noqa: E402

from deepfake_detection_tpu.utils.metrics import auc  # noqa: E402


def _synthetic_clips(n, rng, size=65):
    """4-frame 12-channel clips whose label is a simple luminance rule
    (separable, so 200 steps suffice to learn it)."""
    x = rng.normal(size=(n, 12, size, size)).astype(np.float32) * 0.3
    y = (rng.random(n) > 0.5).astype(np.int64)
    # real clips (y=1) are brighter in every frame
    x += (y * 0.6 - 0.3)[:, None, None, None]
    return x, y


def _train_torch(tm, x_train, y_train, steps, bs, lr=1e-3):
    import torch
    opt = torch.optim.Adam(tm.parameters(), lr=lr)
    loss_fn = torch.nn.CrossEntropyLoss()
    tm.train()
    for s in range(steps):
        i = (s * bs) % len(x_train)
        xb = torch.from_numpy(x_train[i:i + bs])
        yb = torch.from_numpy(y_train[i:i + bs])
        opt.zero_grad()
        loss_fn(tm(xb), yb).backward()
        opt.step()


def _eval_torch(tm, x_eval, bs=32):
    import torch
    tm.eval()
    with torch.no_grad():
        return np.concatenate(
            [tm(torch.from_numpy(x_eval[i:i + bs])).numpy()
             for i in range(0, len(x_eval), bs)])


def _auc_of(logits, y):
    scores = np.exp(logits[:, 1]) / np.exp(logits).sum(-1)
    return float(auc(jnp.asarray(scores), jnp.asarray(y)))


def _assert_converted_parity(tm, model_name, x_eval, y_eval, t_logits,
                             t_auc):
    """Convert the trained torch checkpoint; assert logit + AUC parity."""
    import jax
    variables = convert_state_dict(tm.state_dict())
    from deepfake_detection_tpu.models import create_model
    fm = create_model(model_name, num_classes=2, in_chans=12)
    x_nhwc = jnp.asarray(np.transpose(x_eval, (0, 2, 3, 1)))
    apply = jax.jit(lambda v, x: fm.apply(v, x, training=False))
    f_logits = np.concatenate(
        [np.asarray(apply(variables, x_nhwc[i:i + 32]))
         for i in range(0, len(x_eval), 32)])
    np.testing.assert_allclose(f_logits, t_logits, atol=5e-3, rtol=1e-2)
    f_auc = _auc_of(f_logits, y_eval)
    assert abs(f_auc - t_auc) < 1e-3, (f_auc, t_auc)
    assert f_auc > 0.9


@pytest.mark.slow
def test_trained_reference_checkpoint_converts_with_auc_parity(tmp_path):
    torch = pytest.importorskip("torch")
    ref = _load_reference_efficientnet()
    torch.manual_seed(0)
    tm = ref.mnasnet_small(num_classes=2, in_chans=12)

    rng = np.random.default_rng(0)
    x_train, y_train = _synthetic_clips(256, rng)
    x_eval, y_eval = _synthetic_clips(128, rng)

    _train_torch(tm, x_train, y_train, steps=200, bs=16)
    t_logits = _eval_torch(tm, x_eval)
    t_auc = _auc_of(t_logits, y_eval)
    # the torch reference must actually have learned the rule, or the
    # comparison below proves nothing
    assert t_auc > 0.9, f"reference failed to learn: AUC {t_auc}"
    _assert_converted_parity(tm, "mnasnet_small", x_eval, y_eval,
                             t_logits, t_auc)


@pytest.mark.slow
def test_trained_flagship_v4_converts_with_auc_parity():
    """VERDICT r4 item 4: the FLAGSHIP family (B7-scaled depth-3.1 stages,
    SE at width 2.0, 256-feature head — efficientnet.py:806-848,1187) must
    carry TRAINED weights through the converter, at reduced 64² resolution
    (the arch, not the res, is what's untested).  64 is deliberately EVEN:
    it regression-covers the round-5 padding fix (static symmetric vs XLA
    SAME window-grid shift) at the flagship's own even-size regime."""
    torch = pytest.importorskip("torch")
    ref = _load_reference_efficientnet()
    torch.manual_seed(0)
    tm = ref.efficientnet_deepfake_v4(num_classes=2, in_chans=12)

    rng = np.random.default_rng(0)
    x_train, y_train = _synthetic_clips(192, rng, size=64)
    x_eval, y_eval = _synthetic_clips(64, rng, size=64)

    _train_torch(tm, x_train, y_train, steps=150, bs=8)
    t_logits = _eval_torch(tm, x_eval, bs=16)
    t_auc = _auc_of(t_logits, y_eval)
    assert t_auc > 0.9, f"reference failed to learn: AUC {t_auc}"
    _assert_converted_parity(tm, "efficientnet_deepfake_v4", x_eval, y_eval,
                             t_logits, t_auc)
