"""Vision Transformer (Flax/NHWC, TPU-native).

The reference has no transformer backbone (SURVEY.md §5: the temporal dim is
channel-concat); ViT-B/16 and ViT-L/16 are here to stress the attention
path, and are the customer for the sequence-parallel
machinery in ``parallel/ring_attention.py``.

TPU notes:
* Attention is pluggable: ``attn_impl='full'`` is single-device dense
  attention; ``'ring'``/``'ulysses'`` shard the token axis over a mesh axis
  via shard_map (``sp_mesh`` + ``seq_axis``), so a 12-block ViT-L forward at
  long sequence runs with O(L/n) activation memory per chip and K/V blocks
  riding ICI neighbor-to-neighbor.
* All matmuls are (B·L, D)×(D, ·) GEMMs on the MXU; LayerNorm and GELU fuse
  into the surrounding dots under XLA.
* Architectural layout (pre-LN, learned pos-embed, optional class token)
  follows the ViT paper / timm conventions, EXCEPT the fused-qkv output
  layout: the 3C columns are HEAD-MAJOR (H, 3, D), not timm's (3, H, D),
  so tensor-parallel sharding of the qkv kernel propagates through the
  reshape (see parallel/tp.py).  A torch ViT checkpoint import must
  permute the qkv kernel/bias columns accordingly
  (tools/convert_torch_checkpoint.py's ViT path does this); loading
  timm-layout columns unpermuted yields silently-wrong logits.
* Checkpoint-parity numerics: LayerNorm ε=1e-5 and exact (erf) GELU match
  torch/timm — both fuse identically under XLA, so parity costs nothing.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.drop import DropPath
from ..ops.flash_attention import flash_attention
from ..parallel.ring_attention import full_attention, ring_self_attention
from ..registry import register_model

__all__ = ["VisionTransformer", "prepare_vit_pipeline",
           "vit_pipeline_forward"]


def _cfg(**kwargs):
    cfg = dict(num_classes=1000, input_size=(3, 224, 224), pool_size=None,
               crop_pct=0.9, interpolation="bicubic",
               mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5),
               first_conv="patch_embed", classifier="head")
    cfg.update(kwargs)
    return cfg


class _Attention(nn.Module):
    """Multi-head self-attention with a pluggable kernel."""
    num_heads: int
    qkv_bias: bool = True
    attn_impl: str = "full"  # 'full'|'flash'|'ring'|'ring_flash'|'ulysses'
    sp_mesh: Any = None           # jax.sharding.Mesh for ring/ulysses
    seq_axis: str = "data"
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        B, L, C = x.shape
        H = self.num_heads
        qkv = nn.Dense(3 * C, use_bias=self.qkv_bias, dtype=self.dtype,
                       name="qkv")(x)
        # head-major fused-qkv layout (H, 3, D), not timm's (3, H, D): under
        # tensor parallelism the qkv kernel's 3C output dim is sharded over
        # the 'model' axis (parallel/tp.py), and only an H-major split lets
        # GSPMD propagate that sharding through this reshape (H % tp == 0;
        # a leading factor 3 would force an all-gather + reshard here)
        qkv = qkv.reshape(B, L, H, 3, C // H)
        q, k, v = (qkv[:, :, :, i] for i in range(3))  # (B, L, H, D)
        if self.attn_impl == "flash":
            # fused Pallas kernel: scores stay in VMEM, O(L) HBM traffic
            out = flash_attention(q, k, v)
        elif self.attn_impl == "full" or self.sp_mesh is None:
            out = full_attention(q, k, v)
        else:
            out = ring_self_attention(q, k, v, self.sp_mesh,
                                      seq_axis=self.seq_axis,
                                      impl=self.attn_impl)
        out = out.reshape(B, L, C)
        return nn.Dense(C, dtype=self.dtype, name="proj")(out)


class _Block(nn.Module):
    """Pre-LN transformer block."""
    num_heads: int
    mlp_ratio: float = 4.0
    qkv_bias: bool = True
    drop_rate: float = 0.0
    drop_path_rate: float = 0.0
    attn_impl: str = "full"
    sp_mesh: Any = None
    seq_axis: str = "data"
    dtype: Any = None

    @nn.compact
    def __call__(self, x, training: bool = False):
        C = x.shape[-1]
        y = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm1")(x)
        y = _Attention(self.num_heads, self.qkv_bias, self.attn_impl,
                       self.sp_mesh, self.seq_axis, dtype=self.dtype,
                       name="attn")(y)
        if self.drop_rate:
            y = nn.Dropout(self.drop_rate, deterministic=not training)(y)
        if self.drop_path_rate:
            y = DropPath(self.drop_path_rate, name="drop_path1")(
                y, training=training)
        x = x + y
        y = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm2")(x)
        y = nn.Dense(int(C * self.mlp_ratio), dtype=self.dtype,
                     name="mlp_fc1")(y)
        y = nn.gelu(y, approximate=False)
        if self.drop_rate:
            y = nn.Dropout(self.drop_rate, deterministic=not training)(y)
        y = nn.Dense(C, dtype=self.dtype, name="mlp_fc2")(y)
        if self.drop_rate:
            y = nn.Dropout(self.drop_rate, deterministic=not training)(y)
        if self.drop_path_rate:
            y = DropPath(self.drop_path_rate, name="drop_path2")(
                y, training=training)
        return x + y


class VisionTransformer(nn.Module):
    """ViT classifier; token or mean pooling, optional sequence parallelism.

    With ``class_token=False`` + ``global_pool='avg'`` the token count is
    exactly (H/p)·(W/p), which keeps the sequence axis divisible by the mesh
    for ring/ulysses sharding.
    """
    patch_size: int = 16
    embed_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_ratio: float = 4.0
    qkv_bias: bool = True
    class_token: bool = True
    global_pool: str = "token"     # 'token' | 'avg'
    num_classes: int = 1000
    in_chans: int = 3
    drop_rate: float = 0.0
    drop_path_rate: float = 0.0
    attn_impl: str = "full"
    sp_mesh: Any = None
    seq_axis: str = "data"
    # remat at block boundaries (same policy surface as EfficientNet's
    # TrainConfig.checkpoint_policy): none | full | dots
    remat_policy: str = "none"
    dtype: Any = None
    default_cfg: Any = None

    @nn.compact
    def __call__(self, x, training: bool = False, features_only: bool = False,
                 pool: bool = True):
        assert x.shape[-1] == self.in_chans, (x.shape, self.in_chans)
        B, H, W, _ = x.shape
        p = self.patch_size
        assert H % p == 0 and W % p == 0, (x.shape, p)
        x = nn.Conv(self.embed_dim, (p, p), strides=(p, p), padding="VALID",
                    dtype=self.dtype, name="patch_embed")(x)
        x = x.reshape(B, -1, self.embed_dim)           # (B, N, C)
        n_tokens = x.shape[1] + (1 if self.class_token else 0)
        if self.class_token:
            cls = self.param("cls_token", nn.initializers.zeros,
                             (1, 1, self.embed_dim))
            x = jnp.concatenate([jnp.broadcast_to(
                cls, (B, 1, self.embed_dim)).astype(x.dtype), x], axis=1)
        pos = self.param("pos_embed",
                         nn.initializers.normal(stddev=0.02),
                         (1, n_tokens, self.embed_dim))
        x = x + pos.astype(x.dtype)
        if self.drop_rate:
            x = nn.Dropout(self.drop_rate, deterministic=not training)(x)
        from .helpers import maybe_remat
        block_cls = maybe_remat(_Block, self.remat_policy)
        feats = []
        for i in range(self.depth):
            # stochastic depth scales linearly over blocks (timm convention)
            dpr = self.drop_path_rate * i / max(self.depth - 1, 1)
            x = block_cls(self.num_heads, self.mlp_ratio, self.qkv_bias,
                          self.drop_rate, dpr, self.attn_impl, self.sp_mesh,
                          self.seq_axis, dtype=self.dtype,
                          name=f"blocks_{i}")(x, training)
            feats.append(x)
        x = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm")(x)
        if features_only:
            feats[-1] = x
            return feats
        if not pool:
            return x
        if self.global_pool == "avg":
            start = 1 if self.class_token else 0
            feat = x[:, start:].mean(axis=1)
        else:
            assert self.class_token, "token pooling needs a class token"
            feat = x[:, 0]
        if self.num_classes <= 0:
            return feat
        return nn.Dense(self.num_classes, dtype=self.dtype, name="head")(feat)


def prepare_vit_pipeline(model: "VisionTransformer", variables, mesh,
                         axis: str = "stage"):
    """One-time prep for :func:`vit_pipeline_forward`: stack the per-block
    param trees and shard them over ``axis`` (each stage holds depth/S
    blocks).  Do this once, not per step — it copies the whole tower."""
    from ..parallel.pp import pipeline_sharding, stack_block_params
    s = mesh.shape[axis]
    assert model.depth % s == 0, \
        f"depth {model.depth} not divisible by {s} pipeline stages"
    stacked = stack_block_params(
        [variables["params"][f"blocks_{i}"] for i in range(model.depth)])
    return jax.device_put(stacked, pipeline_sharding(stacked, mesh, axis))


def vit_pipeline_forward(model: "VisionTransformer", variables, x,
                         mesh, num_microbatches: int = 4,
                         axis: str = "stage", stacked=None):
    """Inference forward with the block tower pipelined over ``axis``.

    Patch embed / positional embed / final norm / head run replicated on
    every stage (tiny); the depth-D block tower runs as a GPipe schedule
    (parallel/pp.py).  Output matches ``model.apply(variables, x,
    training=False)`` — the parity test in tests/test_pp.py pins the two
    paths together; KEEP THIS IN SYNC with VisionTransformer.__call__
    (which cannot be factored into setup()-style shared methods because
    pos_embed's shape depends on the input size).

    Per-stage attention runs ``model.attn_impl`` when it is 'full' or
    'flash'; sequence-parallel impls (ring/ulysses) shard over their own
    mesh axis and do not compose with this helper.  Pass ``stacked`` from
    :func:`prepare_vit_pipeline` to avoid re-stacking the tower per call.
    """
    assert model.attn_impl in ("full", "flash"), \
        f"pipeline forward supports full/flash attention, " \
        f"got {model.attn_impl!r}"
    from ..parallel.pp import gpipe_transformer_tower
    p = variables["params"]
    B = x.shape[0]
    if stacked is None:
        stacked = prepare_vit_pipeline(model, variables, mesh, axis)
    # --- embed (replicated) ---------------------------------------------
    pe = nn.Conv(model.embed_dim, (model.patch_size,) * 2,
                 strides=(model.patch_size,) * 2, padding="VALID",
                 dtype=model.dtype)
    h = pe.apply({"params": p["patch_embed"]}, x)
    h = h.reshape(B, -1, model.embed_dim)
    if model.class_token:
        cls = jnp.broadcast_to(p["cls_token"],
                               (B, 1, model.embed_dim)).astype(h.dtype)
        h = jnp.concatenate([cls, h], axis=1)
    h = h + p["pos_embed"].astype(h.dtype)

    # --- pipelined tower -------------------------------------------------
    block = _Block(model.num_heads, model.mlp_ratio, model.qkv_bias,
                   attn_impl=model.attn_impl, dtype=model.dtype)

    def block_apply(bp, hh):
        return block.apply({"params": bp}, hh, False)

    h = gpipe_transformer_tower(mesh, block_apply, stacked, h,
                                num_microbatches, axis=axis)

    # --- head (replicated) -----------------------------------------------
    h = nn.LayerNorm(epsilon=1e-5, dtype=model.dtype).apply({"params": p["norm"]}, h)
    if model.global_pool == "avg":
        start = 1 if model.class_token else 0
        feat = h[:, start:].mean(axis=1)
    else:
        assert model.class_token, "token pooling needs a class token"
        feat = h[:, 0]
    if model.num_classes <= 0:
        return feat
    return nn.Dense(model.num_classes, dtype=model.dtype).apply(
        {"params": p["head"]}, feat)


# name: (patch, dim, depth, heads)
_VIT_DEFS = {
    "vit_tiny_patch16_224": (16, 192, 12, 3),
    "vit_small_patch16_224": (16, 384, 12, 6),
    "vit_base_patch16_224": (16, 768, 12, 12),
    "vit_base_patch16_384": (16, 768, 12, 12),
    "vit_base_patch32_224": (32, 768, 12, 12),
    "vit_large_patch16_224": (16, 1024, 24, 16),
    "vit_large_patch16_384": (16, 1024, 24, 16),
}


def _register():
    for name, (p, dim, depth, heads) in _VIT_DEFS.items():
        size = 384 if name.endswith("_384") else 224

        def fn(pretrained=False, *, _p=p, _dim=dim, _depth=depth,
               _heads=heads, _size=size, **kwargs):
            kwargs.pop("pretrained", None)
            kwargs.setdefault("default_cfg",
                              _cfg(input_size=(3, _size, _size)))
            return VisionTransformer(patch_size=_p, embed_dim=_dim,
                                     depth=_depth, num_heads=_heads, **kwargs)
        fn.__name__ = name
        fn.__qualname__ = name
        fn.__module__ = __name__
        fn.__doc__ = f"{name} (attention-path stretch model)."
        register_model(fn)


_register()
