"""Fused-depthwise / s2d-stem microbenchmark matrix (PERF.md post-fusion).

Three row families, one JSON line each:

* ``block`` — per-MBConv-stage ``dw-conv → BN affine → SiLU`` latency,
  XLA lowering vs the Pallas fused kernel (ops/depthwise_pallas.py),
  fwd and fwd+bwd, at the B4/flagship stage shapes the PERF.md roofline
  says bind step time;
* ``stem`` — the stride-2 stem conv vs its space-to-depth rewrite
  (ops/conv.py ``space_to_depth_stem_kernel``), the MXU-starvation fix;
* ``step`` — a full forward+backward model step with the flags off vs on,
  the before/after number the per-block rows must explain;
* ``dwgrad`` — the default path's depthwise filter gradient, XLA's own
  against the reduction kernel (``ops/depthwise_pallas.py:depthwise_conv``),
  at every depthwise stage shape of the two benchmark configurations: the
  stage alone (values: forward and ``dx`` bit for bit, ``dW`` against
  float32 / ``highest``), the kernel alone, and the whole MBConv block's
  ``jax.grad`` under either, so the operand's relayout is paid.  The table
  behind ``dw_grad_impl``'s rule (PERF.md section 6, PR 29).

``--aot-bytes KIND,B,HW,CIN,COUT,K,STRIDE`` compiles one block's
``jax.grad`` for a *described* v5e (no chip; under ``--smoke`` for the CPU
backend, which checks the reading and measures nothing) and prints the
ENTRY operations' bytes in their tiled layouts, grouped by module path,
and the operations whose results reach nothing but the depthwise filter
gradient: how a change to this step is sized before any chip call.

CPU-runnable end-to-end (that is what ``--smoke`` and the fast-tier test
exercise: the harness itself cannot rot), but Pallas rows run under the
interpreter off-TPU — orders of magnitude slow and NOT a performance
signal, so every row is stamped ``device``/``interpret`` and the doc
tables only admit rows measured on a real TPU, the same verified-rows
gate INPUT_BENCH.md / SERVE_BENCH.md use.  Usage::

    python tools/bench_blocks.py                  # full matrix
    python tools/bench_blocks.py --smoke          # seconds-scale CI row
    python tools/bench_blocks.py --rows block,step --iters 50   # on TPU
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, H, W, C, kernel, stride): the depthwise stages of the families the
# roofline says are VPU-bound — B4 380² resolutions and the flagship's
# 600²×12 first stages (channel counts after the 2.0 width multiplier)
BLOCK_SHAPES = [
    ("b4_s1_k3", 190, 190, 48, 3, 1),
    ("b4_s2_k3", 190, 190, 144, 3, 2),
    ("b4_s3_k5", 95, 95, 192, 5, 2),
    ("b4_s5_k5", 24, 24, 960, 5, 1),
    ("flagship_s1_k3", 300, 300, 256, 3, 1),
    ("flagship_s2_k3", 300, 300, 384, 3, 2),
]
SMOKE_SHAPES = [("smoke_k3", 16, 16, 32, 3, 1), ("smoke_k5s2", 16, 16, 32, 5, 2)]

# every depthwise stage of the two benchmark configurations, by block:
# (config, kind, batch, H=W, in_chs, out_chs, k, stride, blocks of the model)
DW_STAGES = [
    ("flagship_v4_600", "ds", 3, 300, 256, 32, 3, 1, 1),
    ("flagship_v4_600", "ds", 3, 300, 32, 32, 3, 1, 3),
    ("flagship_v4_600", "ir", 3, 300, 32, 48, 3, 2, 1),
    ("flagship_v4_600", "ir", 3, 150, 48, 48, 3, 1, 6),
    ("flagship_v4_600", "ir", 3, 150, 48, 80, 5, 2, 1),
    ("flagship_v4_600", "ir", 3, 75, 80, 80, 5, 1, 6),
    ("flagship_v4_600", "ir", 3, 75, 80, 160, 3, 2, 1),
    ("flagship_v4_600", "ir", 3, 38, 160, 160, 3, 1, 9),
    ("flagship_v4_600", "ir", 3, 38, 160, 224, 5, 1, 1),
    ("flagship_v4_600", "ir", 3, 38, 224, 224, 5, 1, 9),
    ("flagship_v4_600", "ir", 3, 38, 224, 384, 5, 2, 1),
    ("flagship_v4_600", "ir", 3, 19, 384, 384, 5, 1, 12),
    ("flagship_v4_600", "ir", 3, 19, 384, 640, 3, 1, 1),
    ("flagship_v4_600", "ir", 3, 19, 640, 640, 3, 1, 3),
    ("effnet_b4_380", "ds", 80, 190, 48, 24, 3, 1, 1),
    ("effnet_b4_380", "ds", 80, 190, 24, 24, 3, 1, 1),
    ("effnet_b4_380", "ir", 80, 190, 24, 32, 3, 2, 1),
    ("effnet_b4_380", "ir", 80, 95, 32, 32, 3, 1, 3),
    ("effnet_b4_380", "ir", 80, 95, 32, 56, 5, 2, 1),
    ("effnet_b4_380", "ir", 80, 48, 56, 56, 5, 1, 3),
    ("effnet_b4_380", "ir", 80, 48, 56, 112, 3, 2, 1),
    ("effnet_b4_380", "ir", 80, 24, 112, 112, 3, 1, 5),
    ("effnet_b4_380", "ir", 80, 24, 112, 160, 5, 1, 1),
    ("effnet_b4_380", "ir", 80, 24, 160, 160, 5, 1, 5),
    ("effnet_b4_380", "ir", 80, 24, 160, 272, 5, 2, 1),
    ("effnet_b4_380", "ir", 80, 12, 272, 272, 5, 1, 7),
    ("effnet_b4_380", "ir", 80, 12, 272, 448, 3, 1, 1),
    ("effnet_b4_380", "ir", 80, 12, 448, 448, 3, 1, 1),
]
SMOKE_DW_STAGES = [("smoke", "ir", 3, 9, 8, 8, 3, 1, 1),
                   ("smoke", "ds", 3, 10, 16, 8, 5, 2, 1)]


def _bench(fn, iters, *xs) -> float:
    import jax
    out = fn(*xs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*xs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1000


def _emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


def bench_blocks(args, dev, interpret: bool) -> None:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from deepfake_detection_tpu.ops.depthwise_pallas import fused_depthwise

    dtype = getattr(jnp, args.dtype)
    rng = np.random.default_rng(0)
    shapes = SMOKE_SHAPES if args.smoke else BLOCK_SHAPES
    for name, h, w, c, k, stride in shapes:
        x = jnp.asarray(rng.standard_normal((args.batch, h, w, c)), dtype)
        kern = jnp.asarray(rng.standard_normal((k, k, 1, c)) * 0.1,
                           jnp.float32)
        scale = jnp.asarray(rng.uniform(0.5, 1.5, c), jnp.float32)
        bias = jnp.asarray(rng.uniform(-0.1, 0.1, c), jnp.float32)

        def xla_stage(x, kern, scale, bias):
            pad = (k - 1) // 2
            z = lax.conv_general_dilated(
                x, kern.astype(x.dtype), (stride, stride),
                [(pad, pad), (pad, pad)], feature_group_count=c,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return jax.nn.silu(z * scale.astype(z.dtype)
                               + bias.astype(z.dtype))

        def pallas_stage(x, kern, scale, bias):
            return fused_depthwise(x, kern, scale, bias, stride=stride,
                                   padding=(k - 1) // 2, act="silu",
                                   interpret=interpret or None)

        for impl, fn in (("xla", xla_stage), ("pallas", pallas_stage)):
            try:
                jfn = jax.jit(fn)
                fwd_ms = _bench(jfn, args.iters, x, kern, scale, bias)

                def loss(x, kern, scale, bias, _fn=fn):
                    return _fn(x, kern, scale, bias).astype(
                        jnp.float32).sum()

                grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
                bwd_ms = _bench(grad, args.iters, x, kern, scale, bias)
            except Exception as e:  # noqa: BLE001 — record, continue
                _emit({"row": "block", "name": name, "impl": impl,
                       "error": repr(e)[:300], "device": dev.device_kind})
                continue
            ho, wo = -(-h // stride), -(-w // stride)
            gflop = 2.0 * args.batch * ho * wo * c * k * k / 1e9
            _emit({"row": "block", "name": name, "impl": impl,
                   "shape": f"{args.batch}x{h}x{w}x{c}", "k": k,
                   "stride": stride, "fwd_ms": round(fwd_ms, 3),
                   "fwd_bwd_ms": round(bwd_ms, 3),
                   "fwd_gflops_per_s": round(gflop / fwd_ms * 1000, 1),
                   "dtype": args.dtype, "device": dev.device_kind,
                   "interpret": bool(interpret and impl == "pallas")})


def bench_stem(args, dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from deepfake_detection_tpu.ops.conv import (space_to_depth,
                                                 space_to_depth_stem_kernel)

    dtype = getattr(jnp, args.dtype)
    rng = np.random.default_rng(1)
    size, chans, stem = (64, 3, 16) if args.smoke else (600, 12, 256)
    x = jnp.asarray(rng.standard_normal((args.batch, size, size, chans)),
                    dtype)
    kern = jnp.asarray(rng.standard_normal((3, 3, chans, stem)) * 0.1,
                       jnp.float32)

    def stride2(x, kern):
        return lax.conv_general_dilated(
            x, kern.astype(x.dtype), (2, 2), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def s2d(x, kern):
        k2, pad = space_to_depth_stem_kernel(kern)
        return lax.conv_general_dilated(
            space_to_depth(x), k2.astype(x.dtype), (1, 1), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    for impl, fn in (("stride2", stride2), ("s2d", s2d)):
        fwd_ms = _bench(jax.jit(fn), args.iters, x, kern)
        _emit({"row": "stem", "impl": impl,
               "shape": f"{args.batch}x{size}x{size}x{chans}",
               "stem_chs": stem, "fwd_ms": round(fwd_ms, 3),
               "dtype": args.dtype, "device": dev.device_kind})


def bench_step(args, dev, interpret: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepfake_detection_tpu.models import create_model, init_model

    model_name = args.model
    size = 32 if args.smoke else args.size
    batch = 1 if args.smoke else args.batch
    dtype = getattr(jnp, args.dtype)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((batch, size, size, 3)), dtype)

    variants = [("baseline", {}), ("fused", {"fused_depthwise": "pallas"}),
                ("s2d", {"stem_s2d": True}),
                ("fused+s2d", {"fused_depthwise": "pallas",
                               "stem_s2d": True})]
    variables = None
    for name, kw in variants:
        model = create_model(model_name, num_classes=2, in_chans=3, **kw)
        if variables is None:   # identical tree across variants, init once
            variables = init_model(model, jax.random.PRNGKey(0),
                                   (1, size, size, 3))

        def loss(params, x, _m=model):
            y = _m.apply({"params": params,
                          "batch_stats": variables["batch_stats"]},
                         x, training=False)
            return y.astype(jnp.float32).sum()

        try:
            step = jax.jit(jax.grad(loss))
            ms = _bench(step, args.iters, variables["params"], x)
        except Exception as e:  # noqa: BLE001 — record, continue
            _emit({"row": "step", "impl": name, "model": model_name,
                   "error": repr(e)[:300], "device": dev.device_kind})
            continue
        _emit({"row": "step", "impl": name, "model": model_name,
               "shape": f"{batch}x{size}x{size}x3",
               "fwd_bwd_ms": round(ms, 3), "dtype": args.dtype,
               "device": dev.device_kind,
               "interpret": bool(interpret and "fused" in name)})


def _mbconv(kind: str, out_chs: int, k: int, stride: int, dtype):
    """The block as both benchmark configurations build it (swish, SE 0.25,
    expansion 6 for ``ir``)."""
    from deepfake_detection_tpu.models.efficientnet_blocks import (
        DepthwiseSeparableConv, InvertedResidual)
    common = dict(dw_kernel_size=k, stride=stride, se_ratio=0.25,
                  act="swish", dtype=dtype)
    if kind == "ir":
        return InvertedResidual(out_chs, exp_ratio=6.0, **common)
    return DepthwiseSeparableConv(out_chs, **common)


@contextlib.contextmanager
def _filter_grad_by(impl: str):
    """Every depthwise stage traced inside gets ``impl``: the probe measures
    what the rule then chooses between, so it steps around the rule."""
    from deepfake_detection_tpu.ops import conv
    rule = conv.dw_grad_impl
    conv.dw_grad_impl = lambda *a, **kw: impl
    try:
        yield
    finally:
        conv.dw_grad_impl = rule


def _block_grad(kind, b, hw, cin, cout, k, stride, dtype, impl):
    """(jitted ``jax.grad`` of the block's summed output in its parameters
    and input, abstract arguments)."""
    import jax
    import jax.numpy as jnp
    blk = _mbconv(kind, cout, k, stride, dtype)
    x = jax.ShapeDtypeStruct((b, hw, hw, cin), dtype)
    variables = jax.eval_shape(lambda: blk.init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape, dtype), training=True))

    def loss(params, stats, x):
        y, _ = blk.apply({"params": params, "batch_stats": stats}, x,
                         training=True, mutable=["batch_stats"])
        return y.astype(jnp.float32).sum()

    def grad(params, stats, x):
        with _filter_grad_by(impl):
            return jax.grad(loss, argnums=(0, 2))(params, stats, x)
    return jax.jit(grad), (variables["params"], variables["batch_stats"], x)


def _randn(specs, seed: int):
    import jax
    import jax.numpy as jnp
    leaves, tree = jax.tree.flatten(specs)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        (jax.random.normal(kk, s.shape, jnp.float32) * 0.5).astype(s.dtype)
        for kk, s in zip(keys, leaves)])


def _dwgrad_row(args, interpret, kind, b, hw, cin, cout, k, stride) -> dict:
    """One stage shape of the ``dwgrad`` family: values, the kernel alone,
    the whole block under either implementation."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepfake_detection_tpu.ops.conv import create_conv2d
    from deepfake_detection_tpu.ops.depthwise_pallas import dw_filter_grad

    dtype = getattr(jnp, args.dtype)
    c = cin * 6 if kind == "ir" else cin
    pad = (stride - 1 + k - 1) // 2
    conv = create_conv2d(c, k, stride=stride, padding="", depthwise=True,
                         dtype=dtype, name="conv_dw")
    x = _randn(jax.ShapeDtypeStruct((b, hw, hw, c), dtype), 1)
    w = conv.init(jax.random.PRNGKey(2), x)

    def stage(impl):
        # forward, then both gradients under the forward's own values
        def run(w, x):
            with _filter_grad_by(impl):
                y, vjp = jax.vjp(lambda w, x: conv.apply(w, x), w, x)
            return (y,) + vjp(y)
        return jax.jit(run)(w, x)

    (y0, dw0, dx0), (y1, dw1, dx1) = stage("xla"), stage("kernel")
    ref = jax.jit(lambda w, x, g: jax.vjp(
        lambda w: lax.conv_general_dilated(
            x.astype(jnp.float32), w, (stride, stride), [(pad, pad)] * 2,
            feature_group_count=c,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST), w)[1](
                g.astype(jnp.float32))[0])(
        w["params"]["conv"]["kernel"], x, y0)
    scale = float(jnp.max(jnp.abs(ref)))

    def err(dw):
        return float(jnp.max(jnp.abs(
            dw["params"]["conv"]["kernel"] - ref))) / scale
    row = {"fwd_equal": bool(jnp.array_equal(y0, y1)),
           "dx_equal": bool(jnp.array_equal(dx0, dx1)),
           "dw_err_xla": err(dw0), "dw_err_kernel": err(dw1)}
    alone = jax.jit(lambda x, g: dw_filter_grad(
        x, g, pads=((pad, pad),) * 2, k=k, stride=stride,
        interpret=interpret))
    row["kernel_alone_ms"] = round(_bench(alone, args.iters, x, y0), 4)
    del y0, y1, dw0, dw1, dx0, dx1, x
    for impl in ("xla", "kernel"):
        fn, specs = _block_grad(kind, b, hw, cin, cout, k, stride, dtype,
                                impl)
        row[f"block_{impl}_ms"] = round(
            _bench(fn, args.iters, *_randn(specs, 3)), 4)
    return row


def bench_dwgrad(args, dev, interpret: bool) -> None:
    stages = SMOKE_DW_STAGES if args.smoke else [
        s for s in DW_STAGES if args.config in ("all", s[0])]
    for config, kind, b, hw, cin, cout, k, stride, blocks in stages:
        c = cin * 6 if kind == "ir" else cin
        row = {"row": "dwgrad", "config": config, "kind": kind,
               "stage": f"{b}x{hw}x{hw}x{c}", "k": k, "stride": stride,
               "blocks": blocks, "dtype": args.dtype,
               "device": dev.device_kind, "interpret": interpret}
        try:
            row.update(_dwgrad_row(args, interpret, kind, b, hw, cin, cout,
                                   k, stride))
        except Exception as e:  # noqa: BLE001 — record, continue
            row["error"] = repr(e)[:400]
        _emit(row)


# ---------------------------------------------------------------------------
# --aot-bytes: the compiled block's ENTRY operations, read without a chip
# ---------------------------------------------------------------------------

_ELEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
               "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
               "u64": 8}
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\](?:\{([\d,]*)(?::([^}]*))?\})?")
_FREE = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
_GROUP = re.compile(r"/(conv_dw|conv_pwl|conv_pw|se|bn\d)(?:/|$)")


def tiled_bytes(shape_text: str) -> int:
    """Bytes of every array in an HLO shape (a tuple sums its parts), each
    in its tiled layout: the two minor-most dimensions padded to the first
    tile ``T(a,b)`` (a one-dimensional ``T(n)``: the minor-most to ``n``)."""
    total = 0
    for dt, dims, m2m, tiles in _ARRAY.findall(shape_text):
        if dt not in _ELEM_BYTES:
            continue
        dims = [int(d) for d in dims.split(",") if d]
        order = [int(d) for d in m2m.split(",") if d] if m2m else \
            list(range(len(dims) - 1, -1, -1))
        tile = re.match(r"T\(([\d,]+)\)", tiles or "")
        if tile and dims:
            for d, t in zip(order, reversed(
                    [int(t) for t in tile.group(1).split(",")])):
                dims[d] = -(-dims[d] // t) * t
        n = 1
        for d in dims:
            n *= d
        total += n * _ELEM_BYTES[dt]
    return total


def entry_ops(hlo_text: str):
    """The ENTRY computation's instructions, in order: dicts of ``name``,
    ``opcode``, ``shape`` (text), ``operands`` (names), ``path`` (the
    ``op_name``), ``root``."""
    entry = hlo_text[hlo_text.index("ENTRY "):]
    entry = entry[:entry.index("\n}")]
    ops = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(ROOT )?(%[\w.\-]+) = (.*?) ([\w\-]+)\((.*)$", line)
        if not m:
            continue
        root, name, shape, opcode, rest = m.groups()
        args = rest.split("), ", 1)[0] if "), " in rest else rest
        path = re.search(r'op_name="([^"]*)"', rest)
        ops.append({"name": name, "opcode": opcode, "shape": shape,
                    "operands": re.findall(r"%[\w.\-]+", args),
                    "path": path.group(1) if path else "",
                    "root": bool(root)})
    return ops


def read_entry_bytes(hlo_text: str, k: int, c: int):
    """(all bytes, {group: bytes}, bytes and names of the operations whose
    results reach nothing but the depthwise filter gradient, operations)
    of a compiled block: an operation's bytes are its operands' and its
    result's, each in its tiled layout; parameters, constants, tuples and
    bitcasts move nothing."""
    ops = entry_ops(hlo_text)
    by_name = {o["name"]: o for o in ops}
    users = {}
    for o in ops:
        for a in o["operands"]:
            users.setdefault(a, set()).add(o["name"])
    # the filter gradient: what the ROOT returns in the kernel's own shape
    root = next(o for o in ops if o["root"])
    dw_shape = re.compile(r"f32\[%d,%d,1,%d\]" % (k, k, c))
    only = {a for a in root["operands"]
            if dw_shape.match(by_name[a]["shape"])}
    for o in reversed(ops):
        u = users.get(o["name"], ())
        if u and all(n in only for n in u):
            only.add(o["name"])
    total, groups, only_bytes, only_names = 0, {}, 0, []
    for o in ops:
        if o["opcode"] in _FREE:
            continue
        n = tiled_bytes(o["shape"]) + sum(
            tiled_bytes(by_name[a]["shape"]) for a in o["operands"]
            if a in by_name)
        total += n
        g = _GROUP.search(o["path"])
        g = g.group(1) if g else "other"
        groups[g] = groups.get(g, 0) + n
        if o["name"] in only:
            only_bytes += n
            only_names.append(o["name"])
    return total, groups, only_bytes, only_names, len(ops)


def aot_bytes(args) -> None:
    import jax
    import jax.numpy as jnp

    from deepfake_detection_tpu.ops import depthwise_pallas as dwp

    kind, rest = args.aot_bytes.split(",", 1)
    b, hw, cin, cout, k, stride = (int(v) for v in rest.split(","))
    c = cin * 6 if kind == "ir" else cin
    dtype = getattr(jnp, args.dtype)
    sharding, target = None, "cpu (smoke: the reading, not a measurement)"
    if not args.smoke:
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import \
            compilation_cache as cc
        from jax.sharding import SingleDeviceSharding
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        target = "described v5e:2x2, one chip"
        # a described-device executable cannot be read back from the cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        # compiled, as on the chip: the CPU host would interpret it
        dwp.resolve_interpret = lambda interpret, kernel: False
    for impl in ("xla", "kernel"):
        fn, specs = _block_grad(kind, b, hw, cin, cout, k, stride, dtype,
                                impl)
        if sharding is not None:
            specs = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=sharding), specs)
        compiled = fn.lower(*specs).compile()
        total, groups, only, names, n_ops = read_entry_bytes(
            compiled.as_text(), k, c)
        mem = compiled.memory_analysis()
        _emit({"row": "aot_bytes", "impl": impl, "target": target,
               "block": args.aot_bytes, "dtype": args.dtype,
               "entry_ops": n_ops, "all_mb": round(total / 1e6, 1),
               "by_group_mb": {g: round(v / 1e6, 1)
                               for g, v in sorted(groups.items())},
               "filter_grad_only_mb": round(only / 1e6, 1),
               "filter_grad_only_ops": names,
               "temp_mb": round(getattr(mem, "temp_size_in_bytes", 0)
                                / 1e6, 1)})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--size", type=int, default=380)
    ap.add_argument("--model", default=None,
                    help="step-row model (default: efficientnet_b0, or "
                         "mnasnet_small under --smoke)")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--rows", default="block,stem,step",
                    help="comma list of row families to run "
                         "(block,stem,step,dwgrad)")
    ap.add_argument("--config", default="all",
                    help="dwgrad rows: one benchmark configuration's stages")
    ap.add_argument("--aot-bytes", default=None,
                    metavar="KIND,B,HW,CIN,COUT,K,STRIDE",
                    help="read one compiled block's bytes (ir|ds; no chip) "
                         "and exit, e.g. ir,3,75,80,80,5,1")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI mode: tiny shapes, 2 iters, "
                         "f32 (the harness-can't-rot row)")
    args = ap.parse_args()
    if args.smoke:
        args.iters, args.batch, args.dtype = 2, 2, "float32"
    if args.model is None:
        args.model = "mnasnet_small" if args.smoke else "efficientnet_b0"

    import jax

    if args.aot_bytes:
        aot_bytes(args)
        return
    dev = jax.devices()[0]
    interpret = jax.default_backend() != "tpu"
    if interpret:
        _emit({"note": "non-TPU backend: Pallas rows run under the "
                       "interpreter and are NOT a performance signal "
                       "(doc tables only admit device='TPU *' rows)",
               "device": dev.device_kind})
    rows = set(args.rows.split(","))
    if "block" in rows:
        bench_blocks(args, dev, interpret)
    if "stem" in rows:
        bench_stem(args, dev)
    if "step" in rows:
        bench_step(args, dev, interpret)
    if "dwgrad" in rows:
        bench_dwgrad(args, dev, interpret)


if __name__ == "__main__":
    main()
