"""Model checkpoint helpers.

Re-design of ``/root/reference/dfd/timm/models/helpers.py``: EMA-stream
selection (:13), ``module.``-prefix handling (:19 — a DDP artifact with no JAX
analog, kept only in the torch converter), non-strict shape-mismatch dropping
(:39-43), resume with optimizer/epoch state (:47-73), and pretrained load with
in_chans / classifier surgery (:76-109).

Format: a single msgpack file holding ``{"variables": ..., "meta": {...}}``
(flax.serialization); the training-loop checkpointer (orbax, top-K/best/
recovery) lives in ``train/checkpoint.py``.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Dict, Optional, Tuple

import flax
import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization
from flax.core import freeze, unfreeze

_logger = logging.getLogger(__name__)

__all__ = ["maybe_remat",
           "save_model_checkpoint", "load_state_dict", "load_checkpoint",
           "resume_checkpoint", "load_pretrained", "filter_shape_mismatch",
           "adapt_input_params"]


#: fused-qkv column layout of this codebase (models/vit.py): (H, 3, D)-major.
#: Stamped into checkpoint meta so pre-layout-change checkpoints — whose
#: param shapes are IDENTICAL but whose columns are (3, H, D)-major — are
#: rejected at load instead of silently producing wrong logits.
QKV_LAYOUT = "head_major"


def has_fused_qkv(tree: Any) -> bool:
    """True if a params (sub)tree contains a fused-qkv Dense module."""
    if not isinstance(tree, dict):
        return False
    return any(k == "qkv" and isinstance(v, dict) or has_fused_qkv(v)
               for k, v in tree.items())


def check_qkv_layout(variables: Dict[str, Any], meta: Dict[str, Any],
                     path: str) -> None:
    """Reject transformer checkpoints that predate the head-major layout."""
    if has_fused_qkv(variables.get("params", {})) \
            and meta.get("qkv_layout") != QKV_LAYOUT:
        raise ValueError(
            f"{path}: ViT/TimeSformer checkpoint lacks the "
            f"qkv_layout={QKV_LAYOUT!r} marker, i.e. it predates the "
            f"head-major fused-qkv layout (models/vit.py). Its qkv columns "
            f"are (3, H, D)-major and would load shape-compatibly but "
            f"produce silently-wrong logits. Re-train, or re-convert the "
            f"source torch checkpoint with tools/convert_torch_checkpoint.py.")


def stamp_qkv_layout(meta: Optional[Dict[str, Any]],
                     tree: Dict[str, Any]) -> Dict[str, Any]:
    """Return ``meta`` (copied) with the head-major marker stamped when
    ``tree`` carries fused-qkv params — the single invariant every save
    path must apply so :func:`check_qkv_layout` can verify on load."""
    meta = dict(meta or {})
    if has_fused_qkv(tree.get("params", {})):
        meta.setdefault("qkv_layout", QKV_LAYOUT)
    return meta


def save_model_checkpoint(path: str, variables: Dict[str, Any],
                          meta: Optional[Dict[str, Any]] = None) -> None:
    meta = stamp_qkv_layout(meta, variables)
    variables = unfreeze(variables) if isinstance(
        variables, flax.core.FrozenDict) else variables
    # np-convert only the arrays; meta stays plain python — np.asarray on a
    # str makes a '<U*' scalar that msgpack_restore cannot round-trip
    payload = {"variables": jax.tree.map(np.asarray, variables),
               "meta": meta}
    blob = serialization.msgpack_serialize(payload)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)


def load_state_dict(checkpoint_path: str, use_ema: bool = False) -> Dict[str, Any]:
    """Read a checkpoint file; prefer the EMA stream when asked and present
    (helpers.py:13-28)."""
    if not checkpoint_path or not os.path.isfile(checkpoint_path):
        raise FileNotFoundError(f"No checkpoint at {checkpoint_path!r}")
    with open(checkpoint_path, "rb") as f:
        payload = serialization.msgpack_restore(f.read())
    meta = payload.get("meta", {})
    if "state" in payload and "variables" not in payload:
        # trainer checkpoint (train/checkpoint.py): TrainState state-dict
        # {step, params, batch_stats, opt_state, ema}
        st = payload["state"]
        ema = st.get("ema") or None
        if use_ema and ema:
            _logger.info("Loaded EMA stream from %s", checkpoint_path)
            out = {"params": ema["params"],
                   "batch_stats": ema.get("batch_stats", {})}
        else:
            out = {"params": st["params"],
                   "batch_stats": st.get("batch_stats", {})}
    elif use_ema and "variables_ema" in payload:
        _logger.info("Loaded state_dict_ema from %s", checkpoint_path)
        out = payload["variables_ema"]
    elif use_ema and meta.get("has_ema"):
        _logger.info("Loaded EMA stream from %s", checkpoint_path)
        out = payload.get("variables_ema", payload["variables"])
    else:
        out = payload["variables"]
    check_qkv_layout(out, meta, checkpoint_path)
    return out


def _unflatten(flat: Dict[tuple, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for k, v in flat.items():
        node = tree
        for part in k[:-1]:
            node = node.setdefault(part, {})
        node[k[-1]] = v
    return tree


def _flatten(tree, prefix=()):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flatten(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out


def filter_shape_mismatch(init_vars: Dict[str, Any],
                          loaded_vars: Dict[str, Any]) -> Tuple[Dict[str, Any], int]:
    """Non-strict load: keep the freshly-initialized value wherever the loaded
    tensor's shape disagrees or the key is missing (helpers.py:39-43)."""
    init_flat = _flatten(unfreeze(init_vars) if hasattr(init_vars, "items") else init_vars)
    loaded_flat = _flatten(loaded_vars)
    dropped = 0
    merged = {}
    for k, v in init_flat.items():
        lv = loaded_flat.get(k)
        if lv is not None and tuple(np.shape(lv)) == tuple(np.shape(v)):
            merged[k] = jnp.asarray(lv)
        else:
            if lv is not None:
                _logger.warning("shape mismatch at %s: ckpt %s vs model %s — dropped",
                                "/".join(k), np.shape(lv), np.shape(v))
                dropped += 1
            merged[k] = v
    return _unflatten(merged), dropped


def expand_split_bn(loaded: Dict[str, Any],
                    init_vars: Dict[str, Any]) -> Dict[str, Any]:
    """Adapt a plain-BN checkpoint to a split-BN model tree.

    The reference loads weights FIRST and converts to split BN after
    (convert_splitbn_model deep-copies the pretrained BN into every aux,
    split_batchnorm.py:41-69); a flax tree is fixed at construction, so
    the checkpoint adapts instead: wherever the init tree has
    ``<name>/{main,aux<i>}/bn/<leaf>`` and the checkpoint has
    ``<name>/bn/<leaf>``, the pretrained value fans out to main AND every
    aux.  Non-BN keys pass through untouched.
    """
    init_flat = _flatten(unfreeze(init_vars)
                         if hasattr(init_vars, "items") else init_vars)
    loaded_flat = _flatten(loaded)
    out = dict(loaded_flat)
    for k in init_flat:
        for i, part in enumerate(k):
            if part == "main" or (part.startswith("aux")
                                  and part[3:].isdigit()):
                if k in loaded_flat:
                    break
                # plain-BN checkpoint: <name>/bn/...; split-BN checkpoint
                # with fewer splits: its main seeds the extra aux BNs
                for src in (k[:i] + k[i + 1:],
                            k[:i] + ("main",) + k[i + 1:]):
                    if src in loaded_flat:
                        out[k] = loaded_flat[src]
                        break
                break
    return _unflatten(out)


def load_checkpoint(init_variables: Dict[str, Any], checkpoint_path: str,
                    use_ema: bool = False, strict: bool = True) -> Dict[str, Any]:
    """Load weights into an initialized variable tree (helpers.py:31-44)."""
    loaded = load_state_dict(checkpoint_path, use_ema)
    if strict:
        restored = serialization.from_state_dict(init_variables, loaded) \
            if not isinstance(loaded, dict) else loaded
        # validate structure matches
        init_flat = _flatten(unfreeze(init_variables)
                             if hasattr(init_variables, "items") else init_variables)
        loaded_flat = _flatten(restored)
        missing = set(init_flat) - set(loaded_flat)
        if missing:
            raise KeyError(f"strict load: missing keys {sorted(missing)[:5]} ...")
        merged, dropped = filter_shape_mismatch(init_variables, restored)
        if dropped:
            raise ValueError(f"strict load: {dropped} shape mismatches")
        return merged
    merged, _ = filter_shape_mismatch(init_variables, loaded)
    return merged


def resume_checkpoint(init_variables: Dict[str, Any],
                      checkpoint_path: str) -> Tuple[Dict[str, Any], Dict[str, Any], int]:
    """Full resume (helpers.py:47-73): returns (variables, meta, start_epoch).

    ``meta`` carries optimizer state / epoch / metric written by the training
    checkpointer; start_epoch = saved epoch + 1 (helpers.py:64).
    """
    with open(checkpoint_path, "rb") as f:
        payload = serialization.msgpack_restore(f.read())
    meta = payload.get("meta", {})
    check_qkv_layout(payload["variables"], meta, checkpoint_path)
    variables, _ = filter_shape_mismatch(init_variables, payload["variables"])
    start_epoch = int(meta.get("epoch", -1)) + 1
    _logger.info("Resumed from %s (epoch %d)", checkpoint_path, start_epoch - 1)
    return variables, meta, start_epoch


def adapt_input_params(params: Dict[str, Any], in_chans: int,
                       first_conv: str = "conv_stem") -> Dict[str, Any]:
    """Input-channel surgery for pretrained weights (helpers.py:83-103):
    3→1 chans = sum RGB; 3→N = tile + renormalize.  Kernels are HWIO."""
    params = unfreeze(params) if hasattr(params, "items") else dict(params)

    def visit(node):
        for k, v in node.items():
            if isinstance(v, dict):
                if k == first_conv and "conv" in v and "kernel" in v["conv"]:
                    kern = np.asarray(v["conv"]["kernel"])
                    kh, kw, ci, co = kern.shape
                    if ci == in_chans:
                        continue
                    if in_chans == 1:
                        new = kern.sum(axis=2, keepdims=True)
                    else:
                        reps = int(np.ceil(in_chans / ci))
                        new = np.tile(kern, (1, 1, reps, 1))[:, :, :in_chans]
                        new *= ci / in_chans
                    v["conv"]["kernel"] = jnp.asarray(new)
                else:
                    visit(v)
    visit(params)
    return params


def load_pretrained(init_variables, checkpoint_path: str, num_classes: int,
                    in_chans: int = 3, first_conv: str = "conv_stem",
                    classifier: str = "classifier", strict: bool = True):
    """Pretrained load with input/classifier surgery (helpers.py:76-109).

    The reference pulls from model-zoo URLs; this framework is zero-egress so
    pretrained weights come from a local path.
    """
    loaded = load_state_dict(checkpoint_path)
    if "params" in loaded:
        loaded["params"] = adapt_input_params(loaded["params"], in_chans,
                                              first_conv)
        cls = loaded["params"].get(classifier)
        if cls is not None and "kernel" in cls:
            if np.shape(cls["kernel"])[-1] != num_classes:
                _logger.info("classifier size mismatch — re-initializing head")
                loaded["params"].pop(classifier)
                strict = False
    merged, _ = filter_shape_mismatch(init_variables, loaded)
    return merged


def maybe_remat(block_cls, policy: str):
    """Wrap a block Module class for rematerialization (shared policy
    surface of EfficientNet/ViT/TimeSformer; TrainConfig.checkpoint_policy).

    'none' — save all activations; 'full' — recompute the whole block in
    the backward pass; 'dots' — save only matmul/conv outputs.  Both
    remat policies also keep what the flash attention op names
    (``ops/flash_attention.py:FLASH_RESIDUALS``: its output and one float32
    a row of its statistics) and what the sparse attention op names
    (``ops/sparse_attention.py:SPARSE_RESIDUALS``: its output, its row
    statistics and the selection), so a block that calls either op never
    runs its forward kernels again; a block without them has no such names
    and is rematerialised as before.  Blocks must take ``training`` as their
    second positional argument (static).
    """
    import flax.linen as nn
    from ..ops.flash_attention import FLASH_RESIDUALS
    from ..ops.sparse_attention import SPARSE_RESIDUALS
    assert policy in ("none", "full", "dots"), \
        f"remat policy must be none|full|dots, got {policy!r}"
    if policy == "none":
        return block_cls
    policies = jax.checkpoint_policies
    jpolicy = policies.save_only_these_names(*FLASH_RESIDUALS,
                                              *SPARSE_RESIDUALS)
    if policy == "dots":
        jpolicy = policies.save_from_both_policies(policies.checkpoint_dots,
                                                   jpolicy)
    return nn.remat(block_cls, policy=jpolicy, static_argnums=(2,))
