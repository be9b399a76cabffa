"""The main path's Pallas kernels compile for a v5e chip — no chip needed.

libtpu's compiler is installed in the CPU sandbox and compiles for a
*described* (not attached) ``v5e:2x2`` topology, so Mosaic's verdict on
each kernel at published widths (flagship / B4 depthwise stages, ViT-B/16
attention, Phi-4-mini-flash's attention and selective scan,
granite-4.0-h-micro's state-space dual scan and both models' causal
convolution at 16,384 tokens) is a two-second test instead of a chip call.
Interpret mode
cannot see what this sees: unaligned tiles, VMEM overflow, unsupported
strided accesses.  Nothing runs, so nothing here is a measurement.

Only one process may hold libtpu, so the topology is described inside a
module-scoped fixture (never at import), every compile happens in the
test's own process, and all such tests live in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepfake_detection_tpu.ops import causal_conv, depthwise_pallas, ssd
from deepfake_detection_tpu.ops.causal_conv import causal_conv1d
from deepfake_detection_tpu.ops.conv import dw_grad_scope
from deepfake_detection_tpu.ops.depthwise_pallas import (dw_filter_grad,
                                                         fused_depthwise)
from deepfake_detection_tpu.ops.flash_attention import flash_attention
from deepfake_detection_tpu.ops.selective_scan import selective_scan
from deepfake_detection_tpu.ops.ssd import ssd_scan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable is written to the persistent cache but
    # cannot be read back without a chip — keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "no Mosaic kernel in the compiled program"
    return compiled


# (H=W, C, k, stride): flagship 600² stages, then B4 380² stages — 3×3 and
# 5×5, stride 1 and 2, lane-multiple channel counts and not
_DW_STAGES = [
    pytest.param(300, 256, 3, 1, id="v4-300-c256-k3s1"),
    pytest.param(300, 192, 3, 2, id="v4-300-c192-k3s2"),
    pytest.param(150, 288, 5, 2, id="v4-150-c288-k5s2"),
    pytest.param(38, 1344, 5, 1, id="v4-38-c1344-k5s1"),
    pytest.param(19, 3840, 3, 1, id="v4-19-c3840-k3s1"),
    pytest.param(190, 144, 3, 2, id="b4-190-c144-k3s2"),
    pytest.param(48, 336, 5, 1, id="b4-48-c336-k5s1"),
    pytest.param(24, 960, 5, 2, id="b4-24-c960-k5s2"),
]


def _dw_specs(one_chip, hw, c, k):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return (S((2, hw, hw, c), jnp.bfloat16), S((k, k, 1, c), jnp.float32),
            S((c,), jnp.float32))


@pytest.mark.parametrize("hw,c,k,stride", _DW_STAGES)
def test_depthwise_eval_forward_compiles(one_chip, hw, c, k, stride):
    """Serving form: folded-BN affine + SiLU epilogue inside the kernel."""
    x, w, sb = _dw_specs(one_chip, hw, c, k)
    _compile(lambda x, w, s, b: fused_depthwise(
        x, w, s, b, stride=stride, act="silu", interpret=False),
        x, w, sb, sb)


@pytest.mark.parametrize("hw,c,k,stride", _DW_STAGES)
def test_depthwise_train_grad_compiles(one_chip, hw, c, k, stride):
    """Training form (identity epilogue): primal forward, the dx pass
    through the reused forward kernel and the ``dwgrad`` reduction."""
    x, w, _ = _dw_specs(one_chip, hw, c, k)
    _compile(jax.grad(lambda x, w: fused_depthwise(
        x, w, None, None, stride=stride, act="none",
        interpret=False).astype(jnp.float32).sum(), argnums=(0, 1)), x, w)


@pytest.mark.parametrize("hw,c,k,stride", _DW_STAGES)
def test_depthwise_filter_grad_bf16_compiles(one_chip, hw, c, k, stride):
    """The default path's filter gradient at the flagship's batch: both
    operands bf16 as the step hands them over, padded once in bf16, the
    last H tile masked inside the kernel."""
    pad = (stride - 1 + k - 1) // 2
    ho = (hw + 2 * pad - k) // stride + 1
    x = jax.ShapeDtypeStruct((3, hw, hw, c), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((3, ho, ho, c), jnp.bfloat16, sharding=one_chip)
    compiled = _compile(lambda x, g: dw_filter_grad(
        x, g, pads=((pad, pad),) * 2, k=k, stride=stride, interpret=False),
        x, g)
    assert "f32[3,%d,%d,%d]" % (hw, hw, c) not in compiled.as_text(), \
        "a float32 copy of the operand"


def test_flagship_block_grad_takes_the_filter_grad_kernel(one_chip,
                                                          monkeypatch):
    """The mechanism's tripwire (PR 29): ``jax.grad`` of one flagship
    MBConv, ``InvertedResidual(80 -> 80, k5)`` at ``(3, 75, 75, 80)`` bf16,
    through the default path.  At this batch XLA rewrites the stage
    space-to-batch and, left to itself, feeds the depthwise filter gradient
    a k-fold copy of the expanded activation (``bf16[75,24,10,480,5]``, the
    step's largest temporary: 153.9 MB for this block at the parent); with
    the reduction kernel in its place that array is gone, the kernel is
    filed under ``conv_dw`` and the block needs less scratch.  A later jax
    that changes either compiler's choice shows up here, not in a chip
    run."""
    import re
    from deepfake_detection_tpu.models.efficientnet_blocks import \
        InvertedResidual
    # this process's backend is the CPU: say what the program is for, and
    # have the kernel compiled as on the chip, not interpreted
    monkeypatch.setattr(depthwise_pallas, "resolve_interpret",
                        lambda interpret, kernel: False)
    blk = InvertedResidual(80, dw_kernel_size=5, exp_ratio=6.0,
                           se_ratio=0.25, act="swish", dtype=jnp.bfloat16)
    shape = (3, 75, 75, 80)
    variables = jax.eval_shape(lambda: blk.init(
        jax.random.PRNGKey(0), jnp.zeros(shape, jnp.bfloat16),
        training=True))
    variables, x = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (variables, jax.ShapeDtypeStruct(shape, jnp.bfloat16)))

    def loss(params, stats, x):
        y, _ = blk.apply({"params": params, "batch_stats": stats}, x,
                         training=True, mutable=["batch_stats"])
        return y.astype(jnp.float32).sum()

    def compiled_for(platform):
        with dw_grad_scope(1, platform=platform):
            return jax.jit(jax.grad(loss, argnums=(0, 2))).lower(
                variables["params"], variables["batch_stats"], x).compile()

    kernel, xla = compiled_for("tpu"), compiled_for("cpu")
    text = kernel.as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                       r'op_name="([^"]*)"', text)
    assert calls and all("conv_dw" in c for c in calls), calls
    k_fold = re.compile(r"\[[\d,]*\b480,5\]")
    assert not k_fold.search(text)
    assert "tpu_custom_call" not in xla.as_text()
    # what XLA does with this gradient on its own, while it still does
    assert k_fold.search(xla.as_text())
    assert kernel.memory_analysis().temp_size_in_bytes < \
        xla.memory_analysis().temp_size_in_bytes <= 153.9e6 * 1.05


def test_depthwise_residual_forward_compiles(one_chip):
    """Affine + act under grad: the residual-saving (``want_z``) forward."""
    x, w, sb = _dw_specs(one_chip, 150, 288, 5)
    _compile(jax.grad(lambda x, w, s, b: fused_depthwise(
        x, w, s, b, stride=2, act="silu",
        interpret=False).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3)),
        x, w, sb, sb)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_vit_b16_compiles(one_chip, grad):
    """ViT-B/16 at 224²: 197 tokens, 12 heads of 64, bf16."""
    qkv = jax.ShapeDtypeStruct((8, 197, 12, 64), jnp.bfloat16,
                               sharding=one_chip)

    def attn(q, k, v):
        return flash_attention(q, k, v, interpret=False)
    if grad:
        _compile(jax.grad(lambda q, k, v: attn(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), qkv, qkv, qkv)
    else:
        _compile(attn, qkv, qkv, qkv)


@pytest.mark.parametrize("window,block", [
    (512, 256), (None, 512), (512, 512), (None, 1024)],
    ids=["window-256", "full-512", "window", "full"])
def test_flash_attention_phi4flash_16k_compiles(one_chip, window, block):
    """Phi-4-mini-flash at 16,384 tokens, forward and the fused backward
    (an 8 MiB float32 dQ row resident in VMEM beside the tile):
    40 query heads and 20 key heads of 64 reading 10 value pairs of 128
    through the clamped index maps, bf16 operands, the window layer's shrunk
    grid and the full layer's causal one, at the model's own block sizes
    (512 under the window, 1024 without: a 4 MB float32 score tile) and at
    the ones it had before PR 27."""
    spec = lambda h, d: jax.ShapeDtypeStruct(               # noqa: E731
        (1, 16384, h, d), jnp.bfloat16, sharding=one_chip)
    _compile(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=block, block_k=block,
        dot_dtype=jnp.bfloat16, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), spec(40, 64), spec(20, 64), spec(10, 128))


@pytest.mark.parametrize("l,kernels", [(32768, 2), (32768 + 1024, 3)],
                         ids=["fused-at-the-budget", "split-past-it"])
def test_flash_attention_backward_at_the_dq_rows_budget(one_chip, l,
                                                        kernels):
    """The longest row whose backward is the one fused kernel (a 16 MiB
    float32 dQ row, held twice as an output block, under the launch's 64 MiB)
    compiles, and one block more takes the dK/dV and dQ pair: forward +
    backward are 2 Mosaic calls and 3."""
    spec = jax.ShapeDtypeStruct((1, l, 8, 64), jnp.bfloat16,
                                sharding=one_chip)
    compiled = _compile(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=1024, block_k=1024,
        dot_dtype=jnp.bfloat16, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), spec, spec, spec)
    assert compiled.as_text().count("tpu_custom_call") == kernels


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_selective_scan_phi4flash_16k_compiles(one_chip, grad):
    """Phi-4-mini-flash's recurrence at 16,384 tokens: 5120 channels (five
    blocks of 1024 as dense tiles), 16 states, chunks of 128 (the backward
    kernel's 8.3 MB of rebuilt states in VMEM)."""
    spec = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)
    args = (spec(1, 16384, 5120, dt=jnp.bfloat16), spec(1, 16384, 5120),
            spec(5120, 16), spec(1, 16384, 16, dt=jnp.bfloat16),
            spec(1, 16384, 16, dt=jnp.bfloat16), spec(5120))

    def scan(*a):
        return selective_scan(*a, chunk=128, impl="pallas", interpret=False)
    if grad:
        _compile(jax.grad(lambda *a: scan(*a).astype(jnp.float32).sum(),
                          argnums=range(6)), *args)
    else:
        _compile(scan, *args)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_ssd_scan_granite4h_16k_compiles(one_chip, grad):
    """granite-4.0-h-micro's recurrence at 16,384 tokens: 64 heads of 64
    channels (eight heads a grid cell, 512 lanes), state 128, the published
    chunks of 256; each cell's padded transposes and 64-lane head slices are
    what interpret mode cannot judge."""
    spec = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)
    args = (spec(1, 16384, 64, 64, dt=jnp.bfloat16), spec(1, 16384, 64),
            spec(64), spec(1, 16384, 128, dt=jnp.bfloat16),
            spec(1, 16384, 128, dt=jnp.bfloat16), spec(64))

    def scan(*a):
        return ssd_scan(*a, chunk=256, impl="pallas", interpret=False)
    if grad:
        _compile(jax.grad(lambda *a: scan(*a).astype(jnp.float32).sum(),
                          argnums=range(6)), *args)
    else:
        _compile(scan, *args)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("channels", [4352, 5120],
                         ids=["granite4h-c4352", "phi4flash-c5120"])
def test_causal_conv_16k_compiles(one_chip, channels, grad):
    """The Mamba layers' causal convolution at 16,384 tokens: granite's
    4,352 channels and phi4's 5,120 in whole rows of 128, bf16 rows and
    float32 taps; the loop over slabs of 256 lanes, the sublane-shifted
    views of a slab and the fold of its rows into eight sublanes are what
    interpret mode cannot judge."""
    spec = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)
    args = (spec(1, 16384, channels, dt=jnp.bfloat16), spec(4, channels),
            spec(channels))

    def conv(*a):
        return causal_conv1d(*a, impl="pallas", interpret=False)
    if grad:
        _compile(jax.grad(lambda *a: conv(*a).astype(jnp.float32).sum(),
                          argnums=range(3)), *args)
    else:
        _compile(conv, *args)


def test_mamba2_layer_grad_takes_the_causal_conv_kernels(one_chip,
                                                         monkeypatch):
    """The mechanism's tripwire (PR 31): ``jax.grad`` of one remat'd
    Mamba-2 layer at ``train_granite4h_long``'s widths and length.  Written
    as a sum of shifted slices the convolution's backward came out of XLA
    as fusions that wrote the four shifted products to memory (several
    whole ``bf16[1,16384,4352]`` outputs) and summed the taps' and the
    bias's gradients over the rows into ``bf16[4352]`` (7.6 ms a layer on
    the chip); with the op both are gone and its two kernels are filed
    under ``ssd_conv``."""
    import functools
    import re
    from deepfake_detection_tpu.models import granite4h as G
    from deepfake_detection_tpu.models.helpers import maybe_remat
    # this process's backend is the CPU: say what the program is for, and
    # have the kernels compiled as on the chip, not interpreted
    for mod in (causal_conv, ssd):
        monkeypatch.setattr(mod, "resolve_interpret",
                            lambda interpret, kernel: False)
    monkeypatch.setattr(causal_conv, "causal_conv_impl", functools.partial(
        causal_conv.causal_conv_impl, backend="tpu"))
    layer = maybe_remat(G._Layer, "full")(
        kind=G.MAMBA, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        d_ff=8192, ssm_heads=64, ssm_head_dim=64, d_state=128, d_conv=4,
        chunk=256, residual_multiplier=0.22,
        attention_multiplier=0.015625, eps=1e-5, scan_impl="pallas",
        dtype=jnp.bfloat16)
    shape = (1, 16384, 2048)
    variables = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros(shape, jnp.bfloat16), False))
    params, x = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (variables["params"], jax.ShapeDtypeStruct(shape, jnp.bfloat16)))
    text = jax.jit(jax.grad(
        lambda p, x: layer.apply({"params": p}, x, False).astype(
            jnp.float32).sum(), argnums=(0, 1))).lower(
                params, x).compile().as_text()
    scoped = [line for line in text.splitlines()
              if re.search(r'op_name="[^"]*ssd_conv', line)
              and re.search(r"\b(fusion|custom-call)\(", line)]
    kernels = [line for line in scoped if "tpu_custom_call" in line]
    # the forward made again under remat and the backward
    assert len(kernels) == 2, [line[:120] for line in scoped]
    for line in scoped:
        outputs = re.split(r"\b(?:fusion|custom-call)\(",
                           line.split(" = ", 1)[1])[0]
        assert "bf16[4352]" not in outputs, line[:300]
        assert outputs.count("bf16[1,16384,4352]") <= 1, line[:300]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_expert_ffn_lfm2moe_16k_compiles(one_chip, grad):
    """The routed experts of ``train_lfm2moe_8k`` at one pass's size: 16,384
    tokens of width 2048, top-4, eight held experts of 1536, both
    capacities.  The grouped-product kernels' tiles against the 16 MB of
    scoped VMEM are what interpret mode cannot judge: the weights'
    gradient, float32 out of its kernel, did not fit tiles of 1024 x 1024
    (PR 32)."""
    from deepfake_detection_tpu.ops.moe import Routing, expert_ffn
    spec = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)
    args = (spec(16384, 2048, dt=jnp.bfloat16), spec(16384, 4),
            spec(8, 2048, 3072), spec(8, 1536, 2048))
    sel = spec(16384, 4, dt=jnp.int32)

    def ffn(z, w, w13, w2, sel):
        return expert_ffn(z, Routing(sel, w), w13, w2, (0, 8), 64,
                          impl="pallas", interpret=False)[0]
    if grad:
        _compile(jax.grad(lambda *a: ffn(*a).astype(jnp.float32).sum(),
                          argnums=range(4)), *args, sel)
    else:
        _compile(ffn, *args, sel)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_glm47flash_mla_8k_compiles(one_chip, grad):
    """GLM-4.7-Flash's latent attention at 8,192 tokens: 20 heads whose
    keys are 256 wide (192 of their own and the 64 of the shared rotary
    key) and whose values are 256 wide, two lane tiles a head where every
    other cell has one, blocks of 1024, the scale 1/16 folded into q, and
    the fused backward (an 8 MiB float32 dQ row beside the tile): forward
    + backward are 2 Mosaic calls."""
    spec = jax.ShapeDtypeStruct((1, 8192, 20, 256), jnp.bfloat16,
                                sharding=one_chip)

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=1 / 16,
                               block_q=1024, block_k=1024,
                               dot_dtype=jnp.bfloat16, interpret=False)
    if grad:
        compiled = _compile(jax.grad(lambda q, k, v: attn(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), spec, spec, spec)
        assert compiled.as_text().count("tpu_custom_call") == 2
    else:
        _compile(attn, spec, spec, spec)


def test_glm47flash_step_fits_the_chip(one_chip, monkeypatch, tmp_path):
    """The runner's whole train step of ``train_glm47flash_mla`` (the
    configuration's own flags: 4 microbatches of one 8,192-token row),
    every kernel compiled as on the chip: the bytes the buffer assignment
    allocates within 13.46 GB, the attention of all five layers and the
    grouped products of all four expert layers in Mosaic.

    The bound is what the step allocated before remat kept the flash op's
    output and row statistics (12.96 GB; 13.15 GB peak on a v5e) plus
    0.5 GB: the five saved attention outputs are 84 MB a layer in bf16
    (13.42 GB with them; 13.60 GB peak on a v5e).  It reads the compiler's
    memory-usage report, not ``memory_analysis()``, which counts the loop
    body's saved values about twice (14.91 GB before them, 15.82 GB
    with)."""
    import functools
    import importlib
    import json
    import re

    from deepfake_detection_tpu.config import TrainConfig
    from deepfake_detection_tpu.models import init_model
    from deepfake_detection_tpu.ops import moe
    from deepfake_detection_tpu.parallel import (batch_sharding,
                                                 make_train_mesh,
                                                 replicated_sharding,
                                                 train_state_shardings)
    from deepfake_detection_tpu.runners import train as T
    from deepfake_detection_tpu.train import create_train_state
    for name in ("flash_attention", "causal_conv", "moe"):
        monkeypatch.setattr(
            importlib.import_module("deepfake_detection_tpu.ops." + name),
            "resolve_interpret", lambda interpret, kernel: False)
    monkeypatch.setattr(moe, "moe_impl",
                        functools.partial(moe.moe_impl, backend="tpu"))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "glm47_flash_5l.json")) as f:
        flags = json.load(f)["train_flags"]
    mesh = make_train_mesh(batch=1, model=1,
                           devices=list(one_chip.device_set))
    program = T.build_program(TrainConfig.from_args(flags), mesh=mesh)
    assert program.moe_layers == (4, 0) and program.mla_layers == 5
    assert program.attn_bwd_layers == (5, 0)
    state = jax.eval_shape(lambda: create_train_state(init_model(
        program.model, jax.random.PRNGKey(0), (1, 8), training=True,
        dtype=jnp.int32), program.tx))
    shardings = train_state_shardings(state, mesh, fsdp=False,
                                      axis=program.batch_axis)
    step = T.build_steps(program, shardings)[0]
    ids = jax.ShapeDtypeStruct((program.global_batch, 8192), jnp.int32,
                               sharding=batch_sharding(mesh))
    key = jax.random.PRNGKey(0)
    compiled = step.lower(
        jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), state, shardings), ids, ids,
        jax.ShapeDtypeStruct(key.shape, key.dtype,
                             sharding=replicated_sharding(mesh))).compile(
        compiler_options={"xla_dump_to": str(tmp_path)})
    reports = list(tmp_path.glob("*jit_step*memory-usage-report.txt"))
    assert len(reports) == 1, reports
    used = re.match(r"Total bytes used: (\d+)",
                    reports[0].read_text()).group(1)
    assert 12.0e9 < int(used) <= 13.46e9, used
    lines = compiled.as_text().splitlines()
    mosaic = lambda scope: sum(                              # noqa: E731
        1 for line in lines if "tpu_custom_call" in line
        and re.search(r'op_name="[^"]*' + scope, line))
    # the forward and the fused backward: 2 a layer (remat keeps the
    # forward's output and row statistics, so it does not run again)
    assert mosaic("attn_latent") == 2 * 5
    assert mosaic("moe_experts") > 0


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_sparse_attention_keye_32k_compiles(one_chip, grad):
    """Keye-VL-2.0's learned sparse attention at 32,768 tokens: the
    selection kernel (a (32,768, 128) int32 row of keys in VMEM beside the
    indexer key's whole row; the selection packed one bit a pair and the
    selected scores' log-sum-exp handed over), then the attention of 32
    query heads of 128 over 4 key heads with the indexer's loss: the
    forward, which reads the bits and no operand of the indexer, the loss
    kernel, and the one backward kernel (dQ, dK, dV and the indexer's three
    gradients from each tile's mask, ``p`` and ``dp`` made once), which
    makes the indexer's 16 heads of 64 again a tile."""
    import importlib
    SA = importlib.import_module("deepfake_detection_tpu.ops.sparse_attention")
    spec = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)
    l = 32768
    q, k = spec(1, l, 32, 128), spec(1, l, 4, 128)
    qi, ki, w = spec(1, l, 16, 64), spec(1, l, 64), spec(1, l, 16,
                                                        dt=jnp.float32)
    ints, ilse = spec(1, l, dt=jnp.int32), spec(1, l, dt=jnp.float32)
    bits = spec(1, l // 512, 16, l, dt=jnp.int32)

    def attend(q, k, v, qi, ki, w, thr, cut, ilse, bits):
        o, kl = SA.sparse_attention(q, k, v, qi, ki, w,
                                    SA.Selection(thr, cut, None, ilse, bits),
                                    scale=128 ** -0.5, impl="pallas",
                                    interpret=False)
        return o.astype(jnp.float32).sum() + kl.sum()
    args = (q, k, k, qi, ki, w, ints, ints, ilse, bits)
    if grad:
        compiled = _compile(jax.grad(attend, argnums=range(6)), *args)
        # the backward, with the forward whose output it reads
        assert compiled.as_text().count("tpu_custom_call") == 2
    else:
        compiled = _compile(attend, *args)
        assert compiled.as_text().count("tpu_custom_call") == 2
        _compile(lambda qi, ki, w: SA.select_keys(
            qi, ki, w, 2048, impl="pallas", interpret=False), qi, ki, w)
        # the forward launch's operands: q, k, v and the bits alone
        jaxpr = jax.make_jaxpr(attend)(*args).jaxpr
        fwd = [e for e in _pallas_calls(jaxpr) if e.params[
            "jaxpr"].debug_info.func_name == "_fwd_kernel"]
        assert len(fwd) == 1
        assert [(x.aval.shape, x.aval.dtype) for x in fwd[0].invars] == [
            ((1, 32, l, 128), jnp.bfloat16), ((1, 4, l, 128), jnp.bfloat16),
            ((1, 4, l, 128), jnp.bfloat16), ((1, l // 512, 16, l), jnp.int32)]


def _pallas_calls(jaxpr, found=None):
    """Every ``pallas_call`` equation of ``jaxpr`` and the jaxprs nested in
    it, in order."""
    from jax.extend import core as jcore
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                if isinstance(sub, jcore.ClosedJaxpr):
                    _pallas_calls(sub.jaxpr, found)
                elif isinstance(sub, jcore.Jaxpr):
                    _pallas_calls(sub, found)
    return found


def test_keyevl2_step_fits_the_chip(one_chip, monkeypatch, tmp_path):
    """The runner's whole train step of ``train_keye_dsa_32k`` (the
    configuration's own flags: one 32,768-token row), every kernel compiled
    as on the chip: the bytes the buffer assignment allocates within 15.2
    GB (15.04 GB when written: 7.45 GB of state less the gradients'
    buffers, and the full-capacity branch of the expert layer's switch,
    2.15 GB, at the peak), the sparse-attention kernels of all four layers
    (forward, loss and the one fused backward under ``attn_sparse``; the
    selection under ``dsa_select``: remat keeps the selection, the output
    and the row statistics, so neither forward kernel runs again) and the
    grouped products in Mosaic."""
    import functools
    import importlib
    import json
    import re

    from deepfake_detection_tpu.config import TrainConfig
    from deepfake_detection_tpu.models import init_model
    from deepfake_detection_tpu.ops import moe
    from deepfake_detection_tpu.parallel import (batch_sharding,
                                                 make_train_mesh,
                                                 replicated_sharding,
                                                 train_state_shardings)
    from deepfake_detection_tpu.runners import train as T
    from deepfake_detection_tpu.train import create_train_state
    SA = importlib.import_module("deepfake_detection_tpu.ops.sparse_attention")
    for name in ("flash_attention", "moe", "sparse_attention"):
        monkeypatch.setattr(
            importlib.import_module("deepfake_detection_tpu.ops." + name),
            "resolve_interpret", lambda interpret, kernel: False)
    monkeypatch.setattr(moe, "moe_impl",
                        functools.partial(moe.moe_impl, backend="tpu"))
    monkeypatch.setattr(SA, "sparse_impl",
                        functools.partial(SA.sparse_impl, backend="tpu"))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "keye_vl2_30b_a3b_4l.json")) as f:
        flags = json.load(f)["train_flags"]
    mesh = make_train_mesh(batch=1, model=1,
                           devices=list(one_chip.device_set))
    program = T.build_program(TrainConfig.from_args(flags), mesh=mesh)
    assert program.moe_layers == (4, 0) and program.dsa_layers == 4
    assert program.dsa_fwd_bits_layers == 4
    state = jax.eval_shape(lambda: create_train_state(init_model(
        program.model, jax.random.PRNGKey(0), (1, 8), training=True,
        dtype=jnp.int32), program.tx))
    shardings = train_state_shardings(state, mesh, fsdp=False,
                                      axis=program.batch_axis)
    step = T.build_steps(program, shardings)[0]
    ids = jax.ShapeDtypeStruct((program.global_batch, 32768), jnp.int32,
                               sharding=batch_sharding(mesh))
    key = jax.random.PRNGKey(0)
    compiled = step.lower(
        jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), state, shardings), ids, ids,
        jax.ShapeDtypeStruct(key.shape, key.dtype,
                             sharding=replicated_sharding(mesh))).compile(
        compiler_options={"xla_dump_to": str(tmp_path)})
    reports = list(tmp_path.glob("*jit_step*memory-usage-report.txt"))
    assert len(reports) == 1, reports
    used = re.match(r"Total bytes used: (\d+)",
                    reports[0].read_text()).group(1)
    assert 13.0e9 < int(used) <= 15.2e9, used
    lines = compiled.as_text().splitlines()
    mosaic = lambda scope: sum(                              # noqa: E731
        1 for line in lines if "tpu_custom_call" in line
        and re.search(r'op_name="[^"]*' + scope, line))
    assert mosaic("attn_sparse") == 4 * 3
    assert mosaic("dsa_kl") == 4
    assert mosaic("dsa_select") == 4
    assert mosaic("moe_experts") > 0
