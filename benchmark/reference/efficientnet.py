"""Plain EfficientNet reference: forward, loss and gradients.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the published description (Tan & Le 2019, MBConv + squeeze-excite +
swish, compound scaling) and the deepfake flagship's arch definition as the
configuration file states it.  It imports nothing of the program under test.
It runs block by block: one small jitted function per block shape for the
forward pass and one for its vector-Jacobian product, so the whole model is
never one program and the 12x600x600 flagship fits in float32 beside nothing.

Parameter names follow the tree the program's checkpoints use
(``blocks_<stage>_<i>/conv_pw/conv/kernel`` ...), because the benchmark makes
the weights once from the seed and hands the same tree to both sides.

``quant`` is the control's hook: ``None`` computes as stated above, ``"fp8"``
rounds every convolution's and matmul's operands to float8 (e4m3, scaled per
tensor to its largest magnitude) with a straight-through gradient, i.e. the
precision one step below the bfloat16 that the configurations state.
"""

from __future__ import annotations

import functools
import math
import re
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# architecture: arch strings -> a flat list of block specs
# ---------------------------------------------------------------------------

def make_divisible(v: float, divisor: int = 8) -> int:
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


def decode_blocks(arch: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``arch`` is the configuration file's ``arch`` group.  Returns one dict
    per block: name, type (ds|ir), k, stride, exp, cin, cout, se."""
    cm, dm = float(arch["channel_multiplier"]), float(arch["depth_multiplier"])
    cin = make_divisible(arch["stem_size"] * cm)
    blocks: List[Dict[str, Any]] = []
    for si, stage in enumerate(arch["arch_def"]):
        assert len(stage) == 1, "one block definition per stage"
        ops = stage[0].split("_")
        opt = {}
        for op in ops[1:]:
            m = re.match(r"([a-z]+)([\d.]+)", op)
            opt[m.group(1)] = m.group(2)
        repeats = int(math.ceil(int(opt.get("r", 1)) * dm))
        cout = make_divisible(int(opt["c"]) * cm)
        for bi in range(repeats):
            blocks.append(dict(
                name=f"blocks_{si}_{bi}", type=ops[0], k=int(opt["k"]),
                stride=int(opt["s"]) if bi == 0 else 1,
                exp=float(opt.get("e", 1)), cin=cin, cout=cout,
                se=float(opt.get("se", 0))))
            cin = cout
    return blocks


def model_spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    arch = cfg["arch"]
    cm = float(arch["channel_multiplier"])
    return dict(
        blocks=decode_blocks(arch),
        stem=make_divisible(arch["stem_size"] * cm),
        features=make_divisible(arch["num_features_base"] * cm),
        in_chans=int(cfg["input_size"][0]),
        num_classes=int(arch["num_classes"]),
        bn_eps=float(arch["bn_eps"]), bn_momentum=float(arch["bn_momentum"]))


def _mid(b) -> int:
    return make_divisible(b["cin"] * b["exp"]) if b["type"] == "ir" else b["cin"]


def _se_reduced(b) -> int:
    v = b["cin"] * b["se"]
    r = max(1, int(v + 0.5))
    return r + 1 if r < 0.9 * v else r


def param_shapes(spec) -> Tuple[Dict, Dict]:
    """(params, batch_stats) trees of shapes, named as the checkpoints are."""
    def conv(kh, ci, co, bias=False):
        d = {"conv": {"kernel": (kh, kh, ci, co)}}
        if bias:
            d["conv"]["bias"] = (co,)
        return d

    def bn(c):
        return {"bn": {"scale": (c,), "bias": (c,)}}, \
            {"bn": {"mean": (c,), "var": (c,)}}

    P: Dict[str, Any] = {}
    S: Dict[str, Any] = {}

    def add_bn(pd, sd, name, c):
        pd[name], sd[name] = bn(c)

    P["conv_stem"], S["conv_stem"] = {"conv": conv(3, spec["in_chans"],
                                                   spec["stem"])}, {}
    add_bn(P["conv_stem"], S["conv_stem"], "bn1", spec["stem"])
    for b in spec["blocks"]:
        p, s = {}, {}
        mid = _mid(b)
        if b["type"] == "ir":
            p["conv_pw"] = conv(1, b["cin"], mid)
            add_bn(p, s, "bn1", mid)
            p["conv_dw"] = conv(b["k"], 1, mid)
            add_bn(p, s, "bn2", mid)
            p["conv_pwl"] = conv(1, mid, b["cout"])
            add_bn(p, s, "bn3", b["cout"])
        else:
            p["conv_dw"] = conv(b["k"], 1, mid)
            add_bn(p, s, "bn1", mid)
            p["conv_pw"] = conv(1, mid, b["cout"])
            add_bn(p, s, "bn2", b["cout"])
        if b["se"] > 0:
            r = _se_reduced(b)
            p["se"] = {"conv_reduce": conv(1, mid, r, bias=True),
                       "conv_expand": conv(1, r, mid, bias=True)}
        P[b["name"]], S[b["name"]] = p, s
    last = spec["blocks"][-1]["cout"]
    P["conv_head"] = conv(1, last, spec["features"])
    add_bn(P, S, "bn2", spec["features"])
    P["classifier"] = {"kernel": (spec["features"], spec["num_classes"]),
                       "bias": (spec["num_classes"],)}
    return P, S


def residual_gains(spec, gain: float = 0.2) -> Dict[Tuple[str, ...], float]:
    """The scale of the last batch-norm of every block that has a skip
    connection, for the benchmark's weight generator."""
    out = {}
    for b in spec["blocks"]:
        if b["stride"] == 1 and b["cin"] == b["cout"]:
            last = "bn3" if b["type"] == "ir" else "bn2"
            out[(b["name"], last, "bn", "scale")] = gain
    return out


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 after scaling the tensor's largest magnitude to
    the format's largest (448); straight-through gradient."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, 448.0 / amax, 1.0)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)


def _bf16(x):
    q = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _grad_e5m2(x):
    """Identity whose cotangent is rounded to float8 e5m2 (scaled per
    tensor): the gradient operand of a float8 training recipe."""
    return x


def _grad_e5m2_fwd(x):
    return x, None


def _grad_e5m2_bwd(_, g):
    amax = jnp.max(jnp.abs(g))
    s = jnp.where(amax > 0, 57344.0 / amax, 1.0)
    return ((g * s).astype(jnp.float8_e5m2).astype(jnp.float32) / s,)


_grad_e5m2.defvjp(_grad_e5m2_fwd, _grad_e5m2_bwd)

# quant -> (operands of a convolution or matmul, its output, other
# activations).  "fp8" is a float8 training recipe put in bfloat16's place:
# e4m3 operands forward, e5m2 gradient operands backward, bfloat16 between.
_ID = lambda x: x                                          # noqa: E731
_QUANT = {None: _ID, "fp8": _fp8, "bf16": _bf16}
_CONV_OUT = {None: _ID, "bf16": _ID,
             "fp8": lambda y: _bf16(_grad_e5m2(y))}
_ACT = {None: _ID, "bf16": _ID, "fp8": _bf16}


def conv2d(x, k, stride: int = 1, groups: int = 1, quant=None):
    q = _QUANT[quant]
    kh = k.shape[0]
    pad = ((stride - 1) + (kh - 1)) // 2        # static symmetric padding
    return _CONV_OUT[quant](jax.lax.conv_general_dilated(
        q(x), q(k), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST))


def batch_norm_train(x, p, s, eps: float, momentum: float):
    """Batch statistics over (N, H, W); running stats move by ``momentum``
    (torch convention: new = (1 - m) * old + m * batch)."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.maximum(jnp.mean(x * x, axis=(0, 1, 2)) - mean * mean, 0.0)
    y = (x - mean) * jax.lax.rsqrt(var + eps) * p["bn"]["scale"] \
        + p["bn"]["bias"]
    new = {"bn": {"mean": (1 - momentum) * s["bn"]["mean"] + momentum * mean,
                  "var": (1 - momentum) * s["bn"]["var"] + momentum * var}}
    return y, new


def batch_norm_eval(x, p, s, eps: float):
    return (x - s["bn"]["mean"]) * jax.lax.rsqrt(s["bn"]["var"] + eps) \
        * p["bn"]["scale"] + p["bn"]["bias"]


def swish(x):
    return x * jax.nn.sigmoid(x)


def _bn(x, p, s, name, spec, train, new_s, quant=None):
    if train:
        y, new_s[name] = batch_norm_train(x, p[name], s[name], spec["bn_eps"],
                                          spec["bn_momentum"])
        return _ACT[quant](y)
    return _ACT[quant](batch_norm_eval(x, p[name], s[name], spec["bn_eps"]))


def squeeze_excite(x, p, quant):
    z = jnp.mean(x, axis=(1, 2), keepdims=True)
    z = conv2d(z, p["conv_reduce"]["conv"]["kernel"], quant=quant) \
        + p["conv_reduce"]["conv"]["bias"]
    z = swish(z)
    z = conv2d(z, p["conv_expand"]["conv"]["kernel"], quant=quant) \
        + p["conv_expand"]["conv"]["bias"]
    return x * jax.nn.sigmoid(z)


def stem_forward(p, s, x, spec, train: bool, quant=None):
    new_s: Dict[str, Any] = {}
    x = conv2d(x, p["conv"]["conv"]["kernel"], stride=2, quant=quant)
    x = swish(_bn(x, p, s, "bn1", spec, train, new_s, quant))
    return x, new_s


def block_forward(p, s, x, b, spec, train: bool, quant=None):
    """One MBConv (``ir``) or depthwise-separable (``ds``) block."""
    new_s: Dict[str, Any] = {}
    shortcut = x
    mid = _mid(b)
    if b["type"] == "ir":
        x = conv2d(x, p["conv_pw"]["conv"]["kernel"], quant=quant)
        x = swish(_bn(x, p, s, "bn1", spec, train, new_s, quant))
        x = conv2d(x, p["conv_dw"]["conv"]["kernel"], stride=b["stride"],
                   groups=mid, quant=quant)
        x = swish(_bn(x, p, s, "bn2", spec, train, new_s, quant))
        if b["se"] > 0:
            x = squeeze_excite(x, p["se"], quant)
        x = conv2d(x, p["conv_pwl"]["conv"]["kernel"], quant=quant)
        x = _bn(x, p, s, "bn3", spec, train, new_s, quant)
    else:
        x = conv2d(x, p["conv_dw"]["conv"]["kernel"], stride=b["stride"],
                   groups=mid, quant=quant)
        x = swish(_bn(x, p, s, "bn1", spec, train, new_s, quant))
        if b["se"] > 0:
            x = squeeze_excite(x, p["se"], quant)
        x = conv2d(x, p["conv_pw"]["conv"]["kernel"], quant=quant)
        x = _bn(x, p, s, "bn2", spec, train, new_s, quant)
    if b["stride"] == 1 and b["cin"] == b["cout"]:
        x = x + shortcut
    return x, new_s


def head_forward(p, s, x, spec, train: bool, quant=None):
    """conv_head -> BN -> swish -> global average pool -> classifier."""
    new_s: Dict[str, Any] = {}
    x = conv2d(x, p["conv_head"]["conv"]["kernel"], quant=quant)
    x = swish(_bn(x, p, s, "bn2", spec, train, new_s, quant))
    feat = jnp.mean(x, axis=(1, 2))
    q = _QUANT[quant]
    logits = jnp.dot(q(feat), q(p["classifier"]["kernel"]),
                     precision=HIGHEST) + p["classifier"]["bias"]
    return logits, new_s


def soft_target_ce(logits, target):
    return jnp.mean(jnp.sum(-target * jax.nn.log_softmax(logits, -1), -1))


# ---------------------------------------------------------------------------
# the loader's device prologue: cast, normalize, RandomErasing (mode const)
# ---------------------------------------------------------------------------

def normalize(images_u8, mean, std, img_num: int):
    m = jnp.tile(jnp.asarray(mean, jnp.float32) * 255.0, img_num)
    s = jnp.tile(jnp.asarray(std, jnp.float32) * 255.0, img_num)
    return (images_u8.astype(jnp.float32) - m) / s


def _erase_mask(key, h_img: int, w_img: int, prob: float, min_area: float,
                max_area: float, min_aspect: float):
    """Boolean (H, W) mask of the rectangle one frame loses: ten candidate
    rectangles drawn at once, the first that fits is taken (Zhong et al.,
    one rectangle per frame, log-uniform aspect)."""
    la = math.log(min_aspect)
    k_gate, k_count, k_boxes, _ = jax.random.split(key, 4)
    do = jax.random.uniform(k_gate) < prob
    count = jax.random.randint(k_count, (), 1, 2)
    ka, kr, kt, kl = jax.random.split(jax.random.fold_in(k_boxes, 0), 4)
    area = jax.random.uniform(ka, (10,), minval=min_area, maxval=max_area) \
        * (h_img * w_img) / count
    aspect = jnp.exp(jax.random.uniform(kr, (10,), minval=la, maxval=-la))
    hh = jnp.round(jnp.sqrt(area * aspect)).astype(jnp.int32)
    ww = jnp.round(jnp.sqrt(area / aspect)).astype(jnp.int32)
    valid = (ww < w_img) & (hh < h_img)
    pick = jnp.argmax(valid)
    h, w = hh[pick], ww[pick]
    ok = valid[pick] & do
    top = jnp.floor(jax.random.uniform(kt) * (h_img - h + 1)).astype(jnp.int32)
    left = jnp.floor(jax.random.uniform(kl) * (w_img - w + 1)).astype(jnp.int32)
    rows = jnp.arange(h_img)[:, None]
    cols = jnp.arange(w_img)[None, :]
    return ((rows >= top) & (rows < top + h) & (cols >= left)
            & (cols < left + w) & ok)


def random_erase(key, x, img_num: int, prob: float, max_area: float,
                 min_area: float = 0.02, min_aspect: float = 0.3):
    """Each 3-channel frame of each row loses its own rectangle (to zeros)."""
    b, h, w, c = x.shape
    keys = jax.random.split(key, b * img_num).reshape(b, img_num, -1)
    masks = jax.vmap(jax.vmap(lambda k: _erase_mask(
        k, h, w, prob, min_area, max_area, min_aspect)))(keys)
    masks = jnp.repeat(jnp.moveaxis(masks, 1, 3), c // img_num, axis=3)
    return jnp.where(masks, 0.0, x)


def prologue(images_u8, step_index: int, aug: Dict[str, Any], seed: int):
    """What the step is fed, from the uint8 batch the host loader yields."""
    img_num = max(1, images_u8.shape[-1] // 3)
    x = normalize(images_u8, aug["mean"], aug["std"], img_num)
    if aug.get("re_prob", 0) > 0:
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step_index)
        _, ekey = jax.random.split(key)
        x = random_erase(ekey, x, img_num, aug["re_prob"], aug["re_max"])
    return x


# ---------------------------------------------------------------------------
# block-by-block forward / backward
# ---------------------------------------------------------------------------

def _freeze(d):
    """Hashable key of a block or spec; blocks of one shape share a program."""
    return tuple(sorted((k, v) for k, v in d.items() if k != "name"))


@functools.lru_cache(maxsize=None)
def _jitted(kind: str, bkey, skey, train: bool, quant):
    b, spec = dict(bkey) if bkey else None, dict(skey)

    def fwd(p, s, x):
        if kind == "stem":
            return stem_forward(p, s, x, spec, train, quant)
        return block_forward(p, s, x, b, spec, train, quant)

    def bwd(p, s, x, dy):
        _, vjp, _ = jax.vjp(lambda p_, x_: fwd(p_, s, x_), p, x,
                            has_aux=True)
        return vjp(dy)

    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _jitted_head(skey, train: bool, quant):
    spec = dict(skey)

    def loss(p, s, x, y):
        logits, new_s = head_forward(p, s, x, spec, train, quant)
        return soft_target_ce(logits, y), (logits, new_s)

    def fwd_bwd(p, s, x, y):
        (l, (logits, new_s)), (dp, dx) = jax.value_and_grad(
            loss, argnums=(0, 2), has_aux=True)(p, s, x, y)
        return l, logits, new_s, dp, dx

    def logits_only(p, s, x):
        return head_forward(p, s, x, spec, train, quant)[0]

    return jax.jit(fwd_bwd), jax.jit(logits_only)


def _skey(spec):
    return _freeze({k: v for k, v in spec.items() if k != "blocks"})


def _units(spec):
    """(name, kind, block) for the stem and every block, in order."""
    return [("conv_stem", "stem", None)] + \
        [(b["name"], "block", b) for b in spec["blocks"]]


def _head_trees(params, stats):
    hp = {k: params[k] for k in ("conv_head", "bn2", "classifier")}
    hs = {"bn2": stats["bn2"]}
    return hp, hs


def forward_logits(params, stats, x, spec, train: bool = False, quant=None):
    """Inference (or train-mode) forward, block by block.  Returns logits."""
    skey = _skey(spec)
    for name, kind, b in _units(spec):
        fwd, _ = _jitted(kind, _freeze(b) if b else None, skey, train, quant)
        x, _ = fwd(params[name], stats[name], x)
    hp, hs = _head_trees(params, stats)
    return _jitted_head(skey, train, quant)[1](hp, hs, x)


def inference_forward(params, stats, x, spec):
    """The whole inference forward as one traceable function (the FLOP and
    byte walk reads its jaxpr; nothing runs it)."""
    for name, kind, b in _units(spec):
        if kind == "stem":
            x, _ = stem_forward(params[name], stats[name], x, spec, False)
        else:
            x, _ = block_forward(params[name], stats[name], x, b, spec,
                                 False)
    return head_forward(params, stats, x, spec, False)[0]


def loss_and_grads(params, stats, x, y, spec, quant=None):
    """Train-mode loss, gradients of every parameter, and new running stats."""
    skey = _skey(spec)
    inputs, new_stats = [], {}
    for name, kind, b in _units(spec):
        fwd, _ = _jitted(kind, _freeze(b) if b else None, skey, True, quant)
        inputs.append(x)
        x, new_stats[name] = fwd(params[name], stats[name], x)
    hp, hs = _head_trees(params, stats)
    loss, logits, head_s, dhp, dy = _jitted_head(skey, True, quant)[0](
        hp, hs, x, y)
    new_stats.update(head_s)
    grads = dict(dhp)
    for (name, kind, b), xin in zip(reversed(_units(spec)),
                                    reversed(inputs)):
        _, bwd = _jitted(kind, _freeze(b) if b else None, skey, True, quant)
        grads[name], dy = bwd(params[name], stats[name], xin, dy)
    return loss, grads, new_stats, logits
