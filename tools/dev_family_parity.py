"""Dev harness: generic converter parity per backbone family (round 5).

For each (reference torch ctor, flax model name): random-init the torch
model, convert with convert_for_model, compare eval-mode logits at an
EVEN input size.  Prints one status line per family.  Not shipped as a
test — the passing families get a parametrized test in
tests/test_convert_families.py.

    JAX_PLATFORMS=cpu \
        python tools/dev_family_parity.py [family ...]
"""

from __future__ import annotations

import collections.abc
import importlib.util
import os
import sys
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_REF = "/root/reference/dfd/timm"


def load_reference_module(modname: str):
    """Load a reference timm model module standalone (same harness as
    tests/test_convert.py)."""
    import torch  # noqa: F401
    if "torch._six" not in sys.modules:
        six = types.ModuleType("torch._six")
        six.container_abcs = collections.abc
        six.int_classes = int
        six.string_classes = str
        sys.modules["torch._six"] = six

    def load(name, path):
        if name in sys.modules:
            return sys.modules[name]
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        return mod

    if "timm" not in sys.modules:
        timm = types.ModuleType("timm")
        timm.__path__ = [_REF]
        sys.modules["timm"] = timm
        sys.modules["timm.data"] = types.ModuleType("timm.data")
        tmm = types.ModuleType("timm.models")
        tmm.__path__ = [_REF + "/models"]
        sys.modules["timm.models"] = tmm
    # the timm.data stub may have been installed by another harness
    # (tests/test_convert.py) with fewer constants — ensure every constant
    # the model files import exists regardless of who created the stub
    td = sys.modules["timm.data"]
    for name, val in (
            ("IMAGENET_DEFAULT_MEAN", (0.485, 0.456, 0.406)),
            ("IMAGENET_DEFAULT_STD", (0.229, 0.224, 0.225)),
            ("IMAGENET_INCEPTION_MEAN", (0.5,) * 3),
            ("IMAGENET_INCEPTION_STD", (0.5,) * 3),
            ("IMAGENET_DPN_MEAN", tuple(x / 255 for x in (124, 117, 104))),
            ("IMAGENET_DPN_STD", tuple(1 / (.0167 * 255)
                                       for _ in range(3)))):
        if not hasattr(td, name):
            setattr(td, name, val)
    load("timm.models.registry", f"{_REF}/models/registry.py")
    load("timm.models.layers", f"{_REF}/models/layers/__init__.py")
    load("timm.models.helpers", f"{_REF}/models/helpers.py")
    return load(f"timm.models.{modname}", f"{_REF}/models/{modname}.py")


# (reference module, torch ctor, flax model name, input size, atol)
FAMILIES = [
    ("resnet", "resnet18", "resnet18", 64, 1e-4),
    ("resnet", "resnet26d", "resnet26d", 64, 1e-4),   # deep stem + avg_down
    ("resnet", "resnext50_32x4d", "resnext50_32x4d", 64, 1e-4),
    ("senet", "seresnet18", "seresnet18", 64, 1e-4),
    ("senet", "seresnext26_32x4d", "seresnext26_32x4d", 64, 1e-4),
    ("densenet", "densenet121", "densenet121", 64, 1e-4),
    ("dpn", "dpn68", "dpn68", 64, 1e-4),
    ("xception", "xception", "xception", 96, 1e-4),
    ("inception_v3", "inception_v3", "inception_v3", 96, 1e-4),
    ("inception_v4", "inception_v4", "inception_v4", 96, 1e-4),
    ("inception_resnet_v2", "inception_resnet_v2", "inception_resnet_v2",
     96, 1e-4),
    ("res2net", "res2net50_26w_4s", "res2net50_26w_4s", 64, 1e-4),
    ("dla", "dla34", "dla34", 64, 1e-4),
    ("sknet", "skresnet18", "skresnet18", 64, 1e-4),
    ("selecsls", "selecsls42b", "selecsls42b", 64, 1e-4),
    ("hrnet", "hrnet_w18_small", "hrnet_w18_small", 64, 1e-4),
    ("gluon_resnet", "gluon_resnet18_v1b", "gluon_resnet18_v1b", 64, 1e-4),
    ("gluon_xception", "gluon_xception65", "gluon_xception65", 96, 2e-4),
    ("nasnet", "nasnetalarge", "nasnetalarge", 96, 2e-4),
    ("pnasnet", "pnasnet5large", "pnasnet5large", 96, 2e-4),
    # efficientnet-family variants with their own mapping quirks
    ("mobilenetv3", "mobilenetv3_large_100", "mobilenetv3_large_100",
     64, 1e-4),                                    # biased conv head
    ("efficientnet", "mixnet_s", "mixnet_s", 64, 1e-4),   # MixedConv split
    ("efficientnet", "efficientnet_cc_b0_4e", "efficientnet_cc_b0_4e",
     64, 1e-4),                                    # CondConv flat experts
    ("efficientnet", "tf_efficientnet_b0", "tf_efficientnet_b0",
     64, 1e-4),                                    # TF SAME padding path
]


def run_family(mod, ctor, flax_name, size, atol) -> str:
    import torch

    import jax.numpy as jnp
    from convert_torch_checkpoint import convert_for_model
    from deepfake_detection_tpu.models import create_model

    ref = load_reference_module(mod)
    if "_cc_" in ctor:
        # the reference's CondConv2d.forward crashes on this torch version
        # (cond_conv2d.py:93 `.view` on a non-contiguous input); feed it a
        # contiguous tensor so the comparison can run — semantics unchanged
        layers = sys.modules["timm.models.layers"]
        orig = layers.CondConv2d.forward
        if not getattr(layers.CondConv2d, "_contig_patched", False):
            def patched(self, x, rw, _orig=orig):
                return _orig(self, x.contiguous(), rw)
            layers.CondConv2d.forward = patched
            layers.CondConv2d._contig_patched = True
    torch.manual_seed(0)
    # default class count on both sides: several reference entrypoints
    # (dla, hrnet) mishandle a num_classes kwarg or default pretrained=True
    tm = getattr(ref, ctor)(pretrained=False)
    tm.eval()
    # perturb BN stats so eval-mode parity exercises converted running
    # stats, not just the (0, 1) init
    with torch.no_grad():
        for m in tm.modules():
            if isinstance(m, torch.nn.BatchNorm2d):
                m.running_mean.add_(torch.randn_like(m.running_mean) * 0.02)
                m.running_var.mul_(
                    (1 + torch.rand_like(m.running_var) * 0.1))
    variables = convert_for_model(tm.state_dict(), flax_name)
    fm = create_model(flax_name)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, size, size, 3)).astype(np.float32)
    with torch.no_grad():
        t = tm(torch.from_numpy(np.transpose(x, (0, 3, 1, 2)))).numpy()
    f = np.asarray(fm.apply(variables, jnp.asarray(x), training=False))
    err = float(np.abs(f - t).max())
    scale = float(np.abs(t).max())
    ok = err < max(atol, 1e-3 * scale)
    return f"{'OK  ' if ok else 'FAIL'} {ctor:28s} maxerr {err:.2e} " \
           f"(logit scale {scale:.2e})"


def run_inception_v3_fixture(size: int = 96) -> str:
    """Converter parity for inception_v3 WITHOUT torch/torchvision (the
    reference model wraps torchvision, which this image does not ship):
    convert the synthetic torchvision-schema state dict
    (tools/inception_v3_fixture.py) and require full leaf coverage, exact
    shapes, layout-correct values, and a finite forward pass.  Logit
    parity against the torch model is what the OTHER families pin; here
    the torch side cannot execute, so value-level checks verify the
    layout transposes instead."""
    import jax
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict

    from convert_torch_checkpoint import convert_for_model
    from deepfake_detection_tpu.models import create_model
    from inception_v3_fixture import inception_v3_state_dict

    sd = inception_v3_state_dict()
    # convert_for_model raises on ANY uncovered flax leaf / unmatched
    # torch tensor — reaching here already proves coverage is total
    variables = convert_for_model(sd, "inception_v3")
    model = create_model("inception_v3")
    shapes = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, size, size, 3)),
                             training=True),
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)})
    for coll in ("params", "batch_stats"):
        want = flatten_dict(shapes[coll], sep="/")
        got = flatten_dict(variables.get(coll, {}), sep="/")
        if set(want) != set(got):
            return f"FAIL inception_v3(fixture) {coll}: " \
                   f"missing {sorted(set(want) - set(got))[:3]} " \
                   f"extra {sorted(set(got) - set(want))[:3]}"
        bad = [k for k in want
               if tuple(want[k].shape) != tuple(np.shape(got[k]))]
        if bad:
            return f"FAIL inception_v3(fixture) {coll} shapes: {bad[:3]}"
    # layout spot checks: conv OIHW→HWIO, linear (out,in)→(in,out),
    # running stats land in batch_stats
    p, bs = variables["params"], variables["batch_stats"]
    checks = [
        (np.transpose(sd["Conv2d_1a_3x3.conv.weight"], (2, 3, 1, 0)),
         p["conv0"]["conv"]["conv"]["kernel"]),
        (np.transpose(sd["Mixed_6b.branch7x7_2.conv.weight"], (2, 3, 1, 0)),
         p["mixed_6b_b7x7_2"]["conv"]["conv"]["kernel"]),
        (sd["Mixed_5b.branch_pool.bn.running_var"],
         bs["mixed_5b_bpool"]["bn"]["bn"]["var"]),
        (np.transpose(sd["fc.weight"]), p["fc"]["kernel"]),
        (sd["AuxLogits.fc.bias"], p["aux_fc"]["bias"]),
    ]
    for i, (want_a, got_a) in enumerate(checks):
        if not np.array_equal(want_a, np.asarray(got_a)):
            return f"FAIL inception_v3(fixture) value check #{i}"
    logits = np.asarray(model.apply(
        variables, jnp.zeros((1, size, size, 3)), training=False))
    if logits.shape != (1, 1000) or not np.all(np.isfinite(logits)):
        return f"FAIL inception_v3(fixture) forward: {logits.shape}"
    return f"OK   inception_v3(fixture)             " \
           f"{len(sd)} torch tensors -> full coverage, forward finite"


def main() -> None:
    only = set(sys.argv[1:])
    for mod, ctor, flax_name, size, atol in FAMILIES:
        if only and ctor not in only and mod not in only:
            continue
        try:
            if ctor == "inception_v3":
                print(run_inception_v3_fixture(size), flush=True)
            else:
                print(run_family(mod, ctor, flax_name, size, atol),
                      flush=True)
        except Exception as e:  # noqa: BLE001 — survey run, keep going
            print(f"ERR  {ctor:28s} {type(e).__name__}: {str(e)[:160]}",
                  flush=True)


if __name__ == "__main__":
    main()
