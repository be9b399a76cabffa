"""Classification losses.

Parity with ``/root/reference/dfd/timm/loss/`` (cross_entropy.py:6-40,
jsd.py:8-39) plus the reference's loss-selection precedence from the train
runner (``dfd/runners/train.py:506-520``): jsd > mixup(soft-target) >
label-smoothing > plain CE.

All losses are pure jnp functions of ``(logits, target)`` → scalar, so they
jit/grad/vmap and live inside the compiled train step.  Optional
``weight=None`` mask argument supports the padded-eval-batch pattern (TPU
static shapes: pad the last batch and zero out the padding's contribution).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "cross_entropy", "label_smoothing_cross_entropy",
    "soft_target_cross_entropy", "jsd_cross_entropy", "create_loss_fn",
    "one_hot", "next_token_loss",
]


from .utils.metrics import masked_mean as _masked_mean  # canonical helper


def one_hot(labels: jnp.ndarray, num_classes: int,
            on_value: float = 1.0, off_value: float = 0.0) -> jnp.ndarray:
    """Smoothing-aware one-hot (reference mixup.py:5-8)."""
    oh = jax.nn.one_hot(labels, num_classes)
    return oh * on_value + (1.0 - oh) * off_value


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  weight: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Plain CE with integer labels (torch ``nn.CrossEntropyLoss`` analog)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return _masked_mean(nll, weight)


def label_smoothing_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                                  smoothing: float = 0.1,
                                  weight: Optional[jnp.ndarray] = None
                                  ) -> jnp.ndarray:
    """NLL with label smoothing (cross_entropy.py:6-27):
    ``(1-s) * nll + s * mean(-logp)``."""
    assert smoothing < 1.0
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    smooth = -logp.mean(axis=-1)
    return _masked_mean((1.0 - smoothing) * nll + smoothing * smooth, weight)


def soft_target_cross_entropy(logits: jnp.ndarray, target: jnp.ndarray,
                              weight: Optional[jnp.ndarray] = None
                              ) -> jnp.ndarray:
    """CE against soft targets, used under mixup (cross_entropy.py:29-37)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return _masked_mean((-target * logp).sum(axis=-1), weight)


def jsd_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                      num_splits: int = 3, alpha: float = 12.0,
                      smoothing: float = 0.1) -> jnp.ndarray:
    """AugMix JSD consistency loss (jsd.py:8-39).

    Batch is ``num_splits`` stacked views (clean first).  CE on the clean
    split only, plus ``alpha *`` mean KL(p_i ‖ mixture) over all splits.
    """
    split = logits.shape[0] // num_splits
    assert split * num_splits == logits.shape[0]
    clean_logits = logits[:split]
    if smoothing and smoothing > 0:
        loss = label_smoothing_cross_entropy(clean_logits, labels[:split],
                                             smoothing)
    else:
        loss = cross_entropy(clean_logits, labels[:split])
    probs = jax.nn.softmax(logits.reshape(num_splits, split, -1), axis=-1)
    logp_mix = jnp.log(jnp.clip(probs.mean(axis=0), 1e-7, 1.0))
    # torch F.kl_div(input=logq, target=p, 'batchmean') = sum p*(logp-logq)/B
    kl = (probs * (jnp.log(jnp.clip(probs, 1e-7, 1.0)) - logp_mix[None]))
    kl = kl.sum(axis=(1, 2)) / split
    return loss + alpha * kl.mean()


def next_token_loss(hidden: jnp.ndarray, embedding: jnp.ndarray,
                    targets: jnp.ndarray, chunk: int = 2048,
                    weight: Optional[jnp.ndarray] = None,
                    logit_scale: float = 1.0):
    """Mean cross-entropy of ``logit_scale * hidden @ embedding.T`` against
    integer ``targets`` (batch, L), and the token accuracy in percent, over
    the positions whose target is not negative (and whose row ``weight`` is
    not zero).  The logits are made ``chunk`` positions at a time and made
    again in the backward pass, so the (L, rows) float32 matrix is never
    whole: 16,384 x 25,008 of it would be 1.6 GB, its gradient as much
    again.  ``logit_scale`` (a model's 1 / logits_scaling) goes into the
    hidden states once, not into every chunk of logits."""
    b, l, d = hidden.shape
    if logit_scale != 1.0:
        hidden = hidden * jnp.asarray(logit_scale, hidden.dtype)
    chunk = min(chunk, l)
    pad = -l % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    nc = (l + pad) // chunk
    e = embedding.astype(hidden.dtype)
    w = jnp.ones((b,), jnp.float32) if weight is None \
        else weight.astype(jnp.float32)

    @jax.checkpoint
    def one(h, t):
        logits = jnp.einsum("bld,vd->blv", h, e,
                            preferred_element_type=jnp.float32)
        tt = jnp.maximum(t, 0)
        picked = jnp.take_along_axis(logits, tt[..., None], axis=-1)[..., 0]
        valid = (t >= 0).astype(jnp.float32) * w[:, None]
        nll = (jax.nn.logsumexp(logits, axis=-1) - picked) * valid
        hit = (jnp.argmax(logits, axis=-1) == tt).astype(jnp.float32) * valid
        return nll.sum(), hit.sum(), valid.sum()

    nll, hit, n = jax.lax.map(lambda ht: one(*ht), (
        hidden.reshape(b, nc, chunk, d).swapaxes(0, 1),
        targets.reshape(b, nc, chunk).swapaxes(0, 1)))
    n = jnp.maximum(n.sum(), 1.0)
    return nll.sum() / n, 100.0 * hit.sum() / n


def create_loss_fn(cfg) -> Callable:
    """Loss precedence from the reference runner (train.py:506-520)."""
    if getattr(cfg, "jsd", False):
        ns = getattr(cfg, "aug_splits", 0)
        # without view splits the JSD slicing silently corrupts the loss
        # (reference train.py:507 asserts the same)
        assert ns > 1, "--jsd requires --aug-splits > 1"
        return lambda logits, target, weight=None: jsd_cross_entropy(
            logits, target, num_splits=ns, smoothing=cfg.smoothing)
    if getattr(cfg, "mixup", 0.0) > 0:
        # soft targets come from the mixup collate
        return soft_target_cross_entropy
    if getattr(cfg, "smoothing", 0.0) > 0:
        return lambda logits, target, weight=None: \
            label_smoothing_cross_entropy(logits, target, cfg.smoothing,
                                          weight)
    return cross_entropy
