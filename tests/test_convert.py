"""Torch→Flax converter: key mapping, layout transposes, numerical parity.

The parity test instantiates the REFERENCE torch EfficientNet (vendored at
/root/reference, loaded standalone), converts its live state dict, and
compares logits — the strongest checkpoint-bridging evidence available
without the released BaiduYun weights.

Spatial note: at odd input sizes every stride-2 conv sees an odd extent,
where torch's static k//2 padding and XLA's SAME padding coincide exactly;
at even sizes they differ by a one-pixel window shift (documented in
tools/convert_torch_checkpoint.py).
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
from convert_torch_checkpoint import (convert_state_dict,  # noqa: E402
                                      map_key)

_REF = "/root/reference/dfd/timm"


def _load_reference_efficientnet():
    """Reference torch efficientnet module via the importlib harness."""
    torch = pytest.importorskip("torch")
    if not os.path.isdir(_REF):
        pytest.skip(f"reference torch sources not present at {_REF}")
    import collections.abc
    import types
    if "torch._six" not in sys.modules:
        six = types.ModuleType("torch._six")
        six.container_abcs = collections.abc
        six.int_classes = int
        six.string_classes = str
        sys.modules["torch._six"] = six
    if "timm" not in sys.modules:
        timm = types.ModuleType("timm")
        timm.__path__ = [_REF]
        sys.modules["timm"] = timm
        td = types.ModuleType("timm.data")
        td.IMAGENET_DEFAULT_MEAN = (0.485, 0.456, 0.406)
        td.IMAGENET_DEFAULT_STD = (0.229, 0.224, 0.225)
        td.IMAGENET_INCEPTION_MEAN = (0.5,) * 3
        td.IMAGENET_INCEPTION_STD = (0.5,) * 3
        sys.modules["timm.data"] = td
        tmm = types.ModuleType("timm.models")
        tmm.__path__ = [_REF + "/models"]
        sys.modules["timm.models"] = tmm

    def load(name, path):
        if name in sys.modules:
            return sys.modules[name]
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        return mod

    load("timm.models.registry", f"{_REF}/models/registry.py")
    load("timm.models.layers", f"{_REF}/models/layers/__init__.py")
    load("timm.models.helpers", f"{_REF}/models/helpers.py")
    return load("timm.models.efficientnet", f"{_REF}/models/efficientnet.py")


def test_map_key_rules():
    assert map_key("module.conv_stem.weight") == \
        ("params", "conv_stem.conv.conv.kernel")
    assert map_key("bn1.running_mean") == \
        ("batch_stats", "conv_stem.bn1.bn.mean")
    assert map_key("blocks.1.0.conv_pw.weight") == \
        ("params", "blocks_1_0.conv_pw.conv.kernel")
    assert map_key("blocks.1.0.bn3.weight") == \
        ("params", "blocks_1_0.bn3.bn.scale")
    assert map_key("blocks.2.1.se.conv_reduce.bias") == \
        ("params", "blocks_2_1.se.conv_reduce.conv.bias")
    assert map_key("classifier.weight") == ("params", "classifier.kernel")
    assert map_key("bn2.num_batches_tracked") is None


def test_torch_to_flax_numerical_parity():
    """Reference torch efficientnet_b0 logits == converted-flax logits."""
    ref = _load_reference_efficientnet()
    import torch
    tm = ref.efficientnet_b0(num_classes=2)
    tm.eval()
    variables = convert_state_dict(tm.state_dict())

    from deepfake_detection_tpu.models import create_model
    fm = create_model("efficientnet_b0", num_classes=2)

    rng = np.random.default_rng(0)
    # odd size → torch k//2 padding == XLA SAME at every stride-2 conv
    x = rng.normal(size=(2, 65, 65, 3)).astype(np.float32)
    with torch.no_grad():
        t_out = tm(torch.from_numpy(np.transpose(x, (0, 3, 1, 2)))).numpy()
    f_out = np.asarray(fm.apply(
        {"params": variables["params"],
         "batch_stats": variables["batch_stats"]},
        jnp.asarray(x), training=False))
    np.testing.assert_allclose(f_out, t_out, atol=2e-4, rtol=1e-3)


def test_converted_tree_structure_matches_init():
    """Every init param/stat has a converted counterpart of the same shape
    (the --verify mode of the CLI)."""
    ref = _load_reference_efficientnet()
    tm = ref.efficientnet_b0(num_classes=2)
    variables = convert_state_dict(tm.state_dict())

    from flax.traverse_util import flatten_dict

    from deepfake_detection_tpu.models import create_model
    fm = create_model("efficientnet_b0", num_classes=2)
    shapes = jax.eval_shape(
        lambda r: fm.init(r, jnp.zeros((1, 64, 64, 3)), training=True),
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)})
    for coll in ("params", "batch_stats"):
        want = flatten_dict(shapes[coll], sep=".")
        got = flatten_dict(variables[coll], sep=".")
        assert set(want) == set(got), (
            sorted(set(want) - set(got))[:5],
            sorted(set(got) - set(want))[:5])
        for k in want:
            assert tuple(want[k].shape) == tuple(got[k].shape), k


def _build_torch_vit(torch, embed_dim=32, depth=2, num_heads=4,
                     patch=4, img=16, num_classes=2):
    """Minimal torch ViT with timm's module names and fused-qkv layout
    ((3, H, D)-major output columns) — the conversion oracle."""
    nn = torch.nn

    class Attn(nn.Module):
        def __init__(self):
            super().__init__()
            self.qkv = nn.Linear(embed_dim, 3 * embed_dim)
            self.proj = nn.Linear(embed_dim, embed_dim)

        def forward(self, x):
            B, L, C = x.shape
            H, D = num_heads, embed_dim // num_heads
            # timm layout: (B, L, 3, H, D)
            qkv = self.qkv(x).reshape(B, L, 3, H, D).permute(2, 0, 3, 1, 4)
            q, k, v = qkv[0], qkv[1], qkv[2]          # (B, H, L, D)
            a = (q @ k.transpose(-2, -1)) * D ** -0.5
            a = a.softmax(dim=-1)
            out = (a @ v).transpose(1, 2).reshape(B, L, C)
            return self.proj(out)

    class Mlp(nn.Module):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(embed_dim, 4 * embed_dim)
            self.fc2 = nn.Linear(4 * embed_dim, embed_dim)

        def forward(self, x):
            return self.fc2(torch.nn.functional.gelu(self.fc1(x)))

    class Block(nn.Module):
        def __init__(self):
            super().__init__()
            self.norm1 = nn.LayerNorm(embed_dim)
            self.attn = Attn()
            self.norm2 = nn.LayerNorm(embed_dim)
            self.mlp = Mlp()

        def forward(self, x):
            x = x + self.attn(self.norm1(x))
            return x + self.mlp(self.norm2(x))

    class PatchEmbed(nn.Module):
        def __init__(self):
            super().__init__()
            self.proj = nn.Conv2d(3, embed_dim, patch, stride=patch)

        def forward(self, x):
            return self.proj(x).flatten(2).transpose(1, 2)

    class ViT(nn.Module):
        def __init__(self):
            super().__init__()
            n = (img // patch) ** 2 + 1
            self.cls_token = nn.Parameter(torch.zeros(1, 1, embed_dim))
            self.pos_embed = nn.Parameter(
                torch.randn(1, n, embed_dim) * 0.02)
            self.patch_embed = PatchEmbed()
            self.blocks = nn.ModuleList([Block() for _ in range(depth)])
            self.norm = nn.LayerNorm(embed_dim)
            self.head = nn.Linear(embed_dim, num_classes)

        def forward(self, x):
            x = self.patch_embed(x)
            cls = self.cls_token.expand(x.shape[0], -1, -1)
            x = torch.cat([cls, x], dim=1) + self.pos_embed
            for b in self.blocks:
                x = b(x)
            x = self.norm(x)
            return self.head(x[:, 0])

    return ViT()


def test_vit_conversion_numerical_parity():
    """timm-layout torch ViT logits == converted-flax ViT logits — proves
    the (3, H, D) → (H, 3, D) fused-qkv column permute (models/vit.py)."""
    torch = pytest.importorskip("torch")
    torch.manual_seed(0)
    tm = _build_torch_vit(torch)
    tm.eval()
    variables = convert_state_dict(tm.state_dict(), num_heads=4)
    assert not variables["batch_stats"]

    from deepfake_detection_tpu.models.vit import VisionTransformer
    fm = VisionTransformer(patch_size=4, embed_dim=32, depth=2, num_heads=4,
                           num_classes=2)

    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 16, 16, 3)).astype(np.float32)
    with torch.no_grad():
        t_out = tm(torch.from_numpy(np.transpose(x, (0, 3, 1, 2)))).numpy()
    f_out = np.asarray(fm.apply({"params": variables["params"]},
                                jnp.asarray(x), training=False))
    np.testing.assert_allclose(f_out, t_out, atol=2e-4, rtol=1e-3)


def test_vit_qkv_permute_matters():
    """The permute is load-bearing: skipping it changes the logits."""
    torch = pytest.importorskip("torch")
    torch.manual_seed(0)
    tm = _build_torch_vit(torch)
    tm.eval()
    good = convert_state_dict(tm.state_dict(), num_heads=4)
    # num_heads=1 makes the (3, H, D)→(H, 3, D) permute the identity, i.e.
    # an unpermuted (timm-layout) load of the same columns
    bad = convert_state_dict(tm.state_dict(), num_heads=1)

    from deepfake_detection_tpu.models.vit import VisionTransformer
    fm = VisionTransformer(patch_size=4, embed_dim=32, depth=2, num_heads=4,
                           num_classes=2)
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(1, 16, 16, 3)).astype(np.float32))
    out_good = fm.apply({"params": good["params"]}, x, training=False)
    out_bad = fm.apply({"params": bad["params"]}, x, training=False)
    assert float(jnp.abs(out_good - out_bad).max()) > 1e-3


def test_vit_num_heads_resolution_guards(tmp_path):
    """convert_checkpoint refuses ViT checkpoints without a matching ViT
    --model (wrong num_heads would permute shape-compatibly)."""
    torch = pytest.importorskip("torch")
    from convert_torch_checkpoint import _resolve_vit_num_heads
    tm = _build_torch_vit(torch)
    sd = tm.state_dict()
    # non-ViT model name → clear refusal, not AttributeError
    with pytest.raises(SystemExit, match="num_heads"):
        _resolve_vit_num_heads(sd, "efficientnet_b0")
    # ViT name with mismatched dims → refusal naming the mismatch
    with pytest.raises(SystemExit, match="does not match"):
        _resolve_vit_num_heads(sd, "vit_base_patch16_224")


def test_qkv_layout_checkpoint_guard(tmp_path):
    """Model checkpoints with fused qkv are stamped with the layout marker;
    unstamped (pre-layout-change) ones are rejected at load."""
    import jax
    from deepfake_detection_tpu.models.helpers import (
        load_state_dict, save_model_checkpoint)
    from deepfake_detection_tpu.models.vit import VisionTransformer
    fm = VisionTransformer(patch_size=4, embed_dim=32, depth=1, num_heads=4,
                           num_classes=2)
    variables = fm.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3)),
                        training=False)
    good = str(tmp_path / "good.msgpack")
    save_model_checkpoint(good, variables)          # auto-stamps qkv_layout
    out = load_state_dict(good)
    assert "blocks_0" in out["params"]

    # simulate a pre-layout-change checkpoint: same tree, no marker
    from flax import serialization
    bad = str(tmp_path / "old.msgpack")
    with open(bad, "wb") as f:
        f.write(serialization.msgpack_serialize(
            {"variables": jax.tree.map(np.asarray, dict(variables)),
             "meta": {}}))
    with pytest.raises(ValueError, match="qkv_layout"):
        load_state_dict(bad)


def test_flagship_deepfake_v4_conversion():
    """The conversion target that matters: efficientnet_deepfake_v4's full
    tree (12-chan stem 256, head 256) round-trips structurally."""
    ref = _load_reference_efficientnet()
    tm = ref.efficientnet_deepfake_v4(num_classes=2, in_chans=12)
    variables = convert_state_dict(tm.state_dict())

    from flax.traverse_util import flatten_dict

    from deepfake_detection_tpu.models import create_deepfake_model_v4
    fm = create_deepfake_model_v4("efficientnet_deepfake_v4")
    shapes = jax.eval_shape(
        lambda r: fm.init(r, jnp.zeros((1, 64, 64, 12)), training=True),
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)})
    want = flatten_dict(shapes["params"], sep=".")
    got = flatten_dict(variables["params"], sep=".")
    assert set(want) == set(got)
    assert all(tuple(want[k].shape) == tuple(got[k].shape) for k in want)
    stem = variables["params"]["conv_stem"]["conv"]["conv"]["kernel"]
    assert stem.shape == (3, 3, 12, 256)
