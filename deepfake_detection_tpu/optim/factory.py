"""Optimizer factory (optax).

Replaces ``/root/reference/dfd/timm/optim/optim_factory.py:26-100``: the same
name-dispatch surface (sgd / adam / adamw / nadam / radam / adadelta / rmsprop
/ rmsproptf / novograd / nvnovograd, with a ``lookahead_`` prefix), the same
weight-decay parameter split (1-dim params and biases excluded,
``optim_factory.py:11-23``), and the same adamw/radam weight-decay/lr
compensation (:29-33).

The apex ``fused*`` variants (:78-91) dissolve on TPU: every optimizer here is
a pure elementwise pytree transform that XLA fuses inside the jitted train
step, so ``fusedsgd``/``fusedadam``/… alias to their plain counterparts
(``fusedlamb`` → ``optax.lamb``).

The returned transformation is wrapped in ``optax.inject_hyperparams`` so the
scheduler can rewrite ``opt_state.hyperparams['learning_rate']`` between steps
without recompiling (the reference mutates ``param_group['lr']`` the same way,
``scheduler/scheduler.py:81-85``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import optax

from .lookahead import lookahead
from .nvnovograd import nvnovograd
from .rmsprop_tf import rmsprop_tf

__all__ = ["create_optimizer", "weight_decay_mask"]


def weight_decay_mask(params) -> Any:
    """True for leaves that should be decayed: ndim > 1 and not a bias.

    Mirrors ``add_weight_decay`` (optim_factory.py:11-23): 1-dim params (all
    norm scales/biases) and ``bias`` leaves are exempt.  In Flax trees biases
    are 1-dim, so the ndim test subsumes the name test; kept explicit anyway.
    """
    return jax.tree.map(lambda p: getattr(p, "ndim", 0) > 1, params)


def _base_optimizer(name: str, learning_rate, *, opt_eps: float,
                    momentum: float, weight_decay: float, mask,
                    beta2: Optional[float] = None
                    ) -> optax.GradientTransformation:
    """Build one optimizer by (already lowercased, prefix-stripped) name."""
    wd = weight_decay
    # a recipe's second-moment decay (adam, adamw); None keeps optax's own
    betas = {} if beta2 is None else {"b2": beta2}

    if name == "sgd":
        # reference uses nesterov=True (optim_factory.py:48-50)
        tx = optax.chain(
            optax.add_decayed_weights(wd, mask) if wd else optax.identity(),
            optax.sgd(learning_rate, momentum=momentum, nesterov=True),
        )
    elif name == "adam":
        tx = optax.chain(
            optax.add_decayed_weights(wd, mask) if wd else optax.identity(),
            optax.adam(learning_rate, eps=opt_eps, **betas),
        )
    elif name == "adamw":
        tx = optax.adamw(learning_rate, eps=opt_eps, weight_decay=wd,
                         mask=mask, **betas)
    elif name == "nadam":
        tx = optax.chain(
            optax.add_decayed_weights(wd, mask) if wd else optax.identity(),
            optax.nadam(learning_rate, eps=opt_eps),
        )
    elif name == "radam":
        tx = optax.chain(
            optax.add_decayed_weights(wd, mask) if wd else optax.identity(),
            optax.radam(learning_rate, eps=opt_eps),
        )
    elif name == "adadelta":
        tx = optax.chain(
            optax.add_decayed_weights(wd, mask) if wd else optax.identity(),
            optax.adadelta(learning_rate, eps=opt_eps),
        )
    elif name == "rmsprop":
        # torch-style: eps outside sqrt, zero-init accumulator
        tx = optax.chain(
            optax.add_decayed_weights(wd, mask) if wd else optax.identity(),
            optax.rmsprop(learning_rate, decay=0.9, eps=opt_eps,
                          momentum=momentum),
        )
    elif name == "rmsproptf":
        # TF-parity variant; coupled L2 decay goes before the accumulator
        # update, exactly as the reference folds wd into the grad (:91-95)
        tx = optax.chain(
            optax.add_decayed_weights(wd, mask) if wd else optax.identity(),
            rmsprop_tf(learning_rate, alpha=0.9, eps=opt_eps,
                       momentum=momentum),
        )
    elif name in ("novograd", "nvnovograd"):
        # two DISTINCT reference implementations: novograd.py:12 (optax's
        # matches) vs NVIDIA's nvnovograd.py:13 (per-tensor scalar ‖g‖² EMA
        # seeded from the first step — optim/nvnovograd.py here).
        # Neither takes a mask; partition leaves so 1-dim params and biases
        # stay undecayed (reference add_weight_decay, optim_factory.py:35-37).
        # Both normalize per-leaf, so the split is exact.
        def _make(weight_decay):
            if name == "nvnovograd":
                return nvnovograd(learning_rate, eps=opt_eps,
                                  weight_decay=weight_decay)
            return optax.novograd(learning_rate, eps=opt_eps,
                                  weight_decay=weight_decay)
        if wd and mask is not None:
            def _labels(params):
                m = mask(params) if callable(mask) else mask
                return jax.tree.map(
                    lambda b: "decay" if b else "no_decay", m)
            tx = optax.multi_transform(
                {"decay": _make(wd), "no_decay": _make(0.0)}, _labels)
        else:
            tx = _make(wd)
    elif name == "lamb":
        tx = optax.lamb(learning_rate, eps=opt_eps, weight_decay=wd,
                        mask=mask)
    else:
        raise ValueError(f"Invalid optimizer {name!r}")
    return tx


def create_optimizer(cfg, params=None, learning_rate: Optional[float] = None,
                     filter_bias_and_bn: bool = True,
                     inject: bool = True) -> optax.GradientTransformation:
    """Build the optimizer from a TrainConfig-like object.

    ``cfg`` needs: opt, opt_eps, momentum, weight_decay, and (if
    ``learning_rate`` not given) lr; ``opt_beta2`` (adam, adamw) is read if
    it is there.  ``params`` is only used to note that masks are structural
    (callable masks are used, so params may be None).
    """
    del params
    opt_name = cfg.opt.lower()
    weight_decay = cfg.weight_decay
    lr = learning_rate if learning_rate is not None else cfg.lr
    assert lr is not None, "learning rate must be resolved before create_optimizer"

    # adamw/radam wd compensation (optim_factory.py:29-33): the reference keeps
    # the *effective* decay constant w.r.t. lr by pre-dividing.
    if ("adamw" in opt_name or "radam" in opt_name) and weight_decay and lr:
        weight_decay = weight_decay / lr

    parts = opt_name.split("_")
    base_name = parts[-1]
    # apex fused variants alias to plain ones (XLA fuses for free)
    if base_name.startswith("fused"):
        base_name = base_name[len("fused"):] or "sgd"
        base_name = {"adamw": "adamw", "adam": "adam", "sgd": "sgd",
                     "lamb": "lamb", "novograd": "novograd"}.get(base_name,
                                                                 base_name)

    known = ("sgd", "adam", "adamw", "nadam", "radam", "adadelta", "rmsprop",
             "rmsproptf", "novograd", "nvnovograd", "lamb")
    if base_name not in known:
        raise ValueError(f"Invalid optimizer {cfg.opt!r}")

    mask = weight_decay_mask if (filter_bias_and_bn and weight_decay) else None

    def make(learning_rate):
        tx = _base_optimizer(base_name, learning_rate, opt_eps=cfg.opt_eps,
                             momentum=cfg.momentum,
                             weight_decay=weight_decay, mask=mask,
                             beta2=getattr(cfg, "opt_beta2", None))
        if len(parts) > 1 and parts[0] == "lookahead":
            tx = lookahead(tx)
        return tx

    if inject:
        return optax.inject_hyperparams(make)(learning_rate=lr)
    return make(lr)
