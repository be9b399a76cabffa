"""Model factory.

Re-design of ``/root/reference/dfd/timm/models/factory.py`` (252 LoC):
``create_model`` (:8) plus the three deepfake variants that differ only in
defaults (num_classes=2) and checkpoint-loading strictness —
``create_deepfake_model`` (:67), ``_v3`` (:127), ``_v4`` (:190).

Flax split: the factory returns the *architecture* (a flax Module); parameters
live in a separate pytree created by :func:`init_model` (or loaded via
``checkpoint_path``).  ``create_model_and_params`` bundles both for
runner-level convenience.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..registry import is_model, is_model_in_modules, model_entrypoint

__all__ = ["create_model", "create_deepfake_model", "create_deepfake_model_v3",
           "create_deepfake_model_v4", "init_model", "create_model_and_params"]

# modules whose generators understand TF-BN kwargs (factory.py:33-38)
_BN_KWARG_MODULES = ("efficientnet", "mobilenetv3")
# modules that consume the remat policy (TrainConfig.checkpoint_policy)
_REMAT_MODULES = _BN_KWARG_MODULES + ("vit", "timesformer", "phi4flash",
                                        "granite4h", "lfm2moe", "glm4moelite",
                                        "keyevl2")
# modules with a pluggable attention kernel (TrainConfig.attn_impl)
_ATTN_MODULES = ("vit", "timesformer", "phi4flash", "granite4h",
                 "lfm2moe", "glm4moelite", "keyevl2")

_DROP_BLOCK_MODULES = ("resnet", "res2net", "sknet", "gluon_resnet")
_ATTN_IMPLS = ("full", "flash", "ring", "ring_flash", "ulysses")


def create_model(model_name: str, pretrained: bool = False,
                 num_classes: int = 1000, in_chans: int = 3,
                 checkpoint_path: str = "", **kwargs):
    """Build a registered model (factory.py:8-64).

    Filters bn_tf/bn_momentum/bn_eps for non-EfficientNet families and maps the
    legacy ``drop_connect_rate`` onto ``drop_path_rate`` (factory.py:46-50).
    """
    model_args = dict(pretrained=pretrained, num_classes=num_classes,
                      in_chans=in_chans)
    if not is_model_in_modules(model_name, _BN_KWARG_MODULES):
        for k in ("bn_tf", "bn_momentum", "bn_eps"):
            kwargs.pop(k, None)
    if not is_model_in_modules(model_name, _REMAT_MODULES):
        v = kwargs.pop("remat_policy", None)
        if v not in (None, "none"):
            import logging
            logging.getLogger(__name__).warning(
                "remat_policy=%r is only consumed by the %s families; "
                "ignored for %s", v, _REMAT_MODULES, model_name)
    if not is_model_in_modules(model_name, _BN_KWARG_MODULES):
        # the step-time optimization layer rewrites MBConv dw stages and the
        # 3x3-s2 stem — EfficientNet-family-only by construction
        fd = kwargs.pop("fused_depthwise", None)
        s2d = kwargs.pop("stem_s2d", None)
        if fd not in (None, "off") or s2d:
            raise ValueError(
                f"--fused-depthwise/--stem-s2d rewrite the EfficientNet-"
                f"family hot path ({_BN_KWARG_MODULES}); {model_name} has no "
                "depthwise/s2d-stem equivalent — silently training the stock "
                "path would invalidate the perf comparison")
    if (ai := kwargs.get("attn_impl")) is not None:
        if ai not in _ATTN_IMPLS:
            # a typo must not silently fall back to dense attention
            raise ValueError(f"attn_impl={ai!r}: expected one of "
                             f"{_ATTN_IMPLS}")
        if not is_model_in_modules(model_name, _ATTN_MODULES):
            kwargs.pop("attn_impl")
            import logging
            logging.getLogger(__name__).warning(
                "attn_impl=%r is only consumed by the %s families; "
                "ignored for %s", ai, _ATTN_MODULES, model_name)
    if str(kwargs.get("norm_layer", "")).startswith("split") and \
            not is_model_in_modules(model_name, _BN_KWARG_MODULES):
        # the user explicitly asked for AdvProp split-BN semantics —
        # silently training without them would be worse than failing
        raise ValueError(
            f"norm_layer={kwargs['norm_layer']!r} (--split-bn) is only "
            f"supported by the {_BN_KWARG_MODULES} families, not "
            f"{model_name} (the reference's post-hoc convert_splitbn_model "
            "has no flax equivalent)")
    if not is_model_in_modules(model_name, _DROP_BLOCK_MODULES):
        v = kwargs.pop("drop_block_rate", None)
        if v:
            import logging
            logging.getLogger(__name__).warning(
                "drop_block_rate=%r is only consumed by the %s families; "
                "ignored for %s (matches the reference factory's pop of "
                "unsupported drop_block_rate)", v, _DROP_BLOCK_MODULES,
                model_name)
    dcr = kwargs.pop("drop_connect_rate", None)
    if dcr is not None and "drop_path_rate" not in kwargs:
        kwargs["drop_path_rate"] = dcr
    kwargs = {k: v for k, v in kwargs.items() if v is not None}
    if not is_model(model_name):
        raise KeyError(f"Unknown model {model_name!r}")
    model = model_entrypoint(model_name)(**model_args, **kwargs)
    if checkpoint_path:
        # parameters are loaded separately in the flax world; keep the arg for
        # interface parity and surface it via attribute-free convention
        from .helpers import load_checkpoint  # late import, avoids cycle
        model = model  # architecture unchanged; load happens in init path
    return model


def create_deepfake_model(model_name: str = "efficientnet_b7_deepfake",
                          pretrained: bool = False, num_classes: int = 2,
                          in_chans: int = 3, **kwargs):
    """Deepfake default wrapper (factory.py:67-124): num_classes=2."""
    return create_model(model_name, pretrained=pretrained,
                        num_classes=num_classes, in_chans=in_chans, **kwargs)


def create_deepfake_model_v3(model_name: str = "efficientnet_deepfake_v3",
                             pretrained: bool = False, num_classes: int = 2,
                             in_chans: int = 12, **kwargs):
    """v3 wrapper (factory.py:127-187) — asserts its model name (:150)."""
    assert model_name == "efficientnet_deepfake_v3", \
        f"create_deepfake_model_v3 only builds efficientnet_deepfake_v3, got {model_name!r}"
    return create_model(model_name, pretrained=pretrained,
                        num_classes=num_classes, in_chans=in_chans, **kwargs)


def create_deepfake_model_v4(model_name: str = "efficientnet_deepfake_v4",
                             pretrained: bool = False, num_classes: int = 2,
                             in_chans: int = 12, **kwargs):
    """v4 wrapper (factory.py:190-252) — asserts its model name (:213)."""
    assert model_name == "efficientnet_deepfake_v4", \
        f"create_deepfake_model_v4 only builds efficientnet_deepfake_v4, got {model_name!r}"
    return create_model(model_name, pretrained=pretrained,
                        num_classes=num_classes, in_chans=in_chans, **kwargs)


def init_model(model, rng: jax.Array, input_shape: Tuple[int, ...],
               training: bool = False, dtype=jnp.float32) -> Dict[str, Any]:
    """Initialize variables ({'params', 'batch_stats', ...}) for a model.

    ``input_shape`` is NHWC, e.g. ``(1, 600, 600, 12)``.

    The init runs under ``jax.jit``: eager Flax init dispatches every
    constituent op separately (hundreds of device round trips for an
    EfficientNet); one compiled program is a single dispatch, and the
    compile is shared through the persistent compilation cache.
    """
    dummy = jnp.zeros(input_shape, dtype)
    p_rng, d_rng = jax.random.split(rng)

    def _init(p_rng, d_rng, dummy):
        return model.init({"params": p_rng, "dropout": d_rng}, dummy,
                          training=training)

    return jax.jit(_init)(p_rng, d_rng, dummy)


def create_model_and_params(model_name: str, rng: Optional[jax.Array] = None,
                            input_shape: Optional[Tuple[int, ...]] = None,
                            checkpoint_path: str = "", **kwargs):
    """Convenience: build + init (+ optional checkpoint load)."""
    model = create_model(model_name, **kwargs)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if input_shape is None:
        cfg = getattr(model, "default_cfg", None) or {}
        c, h, w = cfg.get("input_size", (3, 224, 224))
        input_shape = (1, h, w, c)
    variables = init_model(model, rng, input_shape)
    if checkpoint_path:
        from .helpers import load_checkpoint
        variables = load_checkpoint(variables, checkpoint_path)
    return model, variables
