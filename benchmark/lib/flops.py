"""Operations and bytes of a model, counted from shapes by the benchmark.

The count walks the jaxpr of the *plain reference's* forward pass (one row),
so it is the work the algorithm needs and not what some compiled program
happens to execute: no rematerialised operation, no padding row and no fused
or unfused epilogue changes it.  A training step is counted as three forward
passes (forward, gradient of the inputs, gradient of the weights), the usual
rule for convolutions and matrix products.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    kh, kw, cin_per_group, _ = eqn.invars[1].aval.shape
    return 2.0 * float(np.prod(out.shape)) * kh * kw * cin_per_group


def _dot_flops(eqn) -> float:
    lhs, out = eqn.invars[0].aval, eqn.outvars[0].aval
    ((lc, _), _) = eqn.params["dimension_numbers"]
    k = float(np.prod([lhs.shape[i] for i in lc]))
    return 2.0 * float(np.prod(out.shape)) * k


def walk(jaxpr, acc: Dict[str, float]) -> None:
    for eqn in jaxpr.eqns:
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr"):
                walk(sub.jaxpr, acc)
            elif hasattr(sub, "eqns"):
                walk(sub, acc)
        name = eqn.primitive.name
        if name == "conv_general_dilated":
            groups = eqn.params["feature_group_count"]
            x, k, y = (eqn.invars[0].aval, eqn.invars[1].aval,
                       eqn.outvars[0].aval)
            f = _conv_flops(eqn)
            if groups > 1 and groups == x.shape[-1]:
                acc["dw_flops"] += f
                acc["dw_in_elems"] += float(np.prod(x.shape))
                acc["dw_out_elems"] += float(np.prod(y.shape))
                acc["dw_kernel_elems"] += float(np.prod(k.shape))
            else:
                acc["dense_flops"] += f
        elif name == "dot_general":
            acc["dense_flops"] += _dot_flops(eqn)


def forward_counts(config: Dict[str, Any]) -> Dict[str, float]:
    """Counts for ONE row through the reference's inference forward."""
    import jax
    import jax.numpy as jnp
    from benchmark import reference
    R = reference.model(config)
    spec = R.model_spec(config)
    pshape, sshape = R.param_shapes(spec)
    as_struct = lambda t: jax.tree.map(           # noqa: E731
        lambda s: jax.ShapeDtypeStruct(tuple(s), jnp.float32), t,
        is_leaf=lambda s: isinstance(s, tuple))
    c, h, w = config["input_size"]
    x = jax.ShapeDtypeStruct((1, h, w, c), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, s, x_: R.inference_forward(p, s, x_, spec))(
            as_struct(pshape), as_struct(sshape), x)
    acc = {k: 0.0 for k in ("dense_flops", "dw_flops", "dw_in_elems",
                            "dw_out_elems", "dw_kernel_elems")}
    walk(jaxpr.jaxpr, acc)
    acc["forward_flops"] = acc["dense_flops"] + acc["dw_flops"]
    return acc


def train_flops_per_row(counts: Dict[str, float]) -> float:
    return 3.0 * counts["forward_flops"]


def dw_train_floor_seconds(counts: Dict[str, float], rows: float,
                           peak: Dict[str, float], bytes_per_elem: int = 2
                           ) -> Dict[str, float]:
    """The least time the chip could take for the depthwise convolutions of
    ``rows`` rows of a training step: forward (read x, write y), gradient of
    the input (read dy, write dx) and gradient of the kernel (read x and dy),
    kernels once each way, against peak FLOP/s and peak bytes/s."""
    flops = 3.0 * counts["dw_flops"] * rows
    elems = rows * (2.0 * counts["dw_in_elems"] + 3.0 * counts["dw_out_elems"]
                    + counts["dw_in_elems"]) + 3.0 * counts["dw_kernel_elems"]
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = elems * bytes_per_elem / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "t_flops": t_flops,
            "t_bytes": t_bytes,
            "bound": "bytes" if t_bytes >= t_flops else "flops"}
