"""FLOP walk of a model's forward pass: the count under the live MFU gauge.

Walks the jaxpr of a forward and classifies every
``conv_general_dilated`` / ``dot_general`` by where it executes on TPU:

* dense convs and matmuls tile onto the MXU (the 128×128 systolic array);
* depthwise convs (``feature_group_count == in_channels``) cannot use the
  MXU — each output element is a k²-tap dot over ONE channel, so they run
  on the VPU at roughly 1-2% of MXU throughput;
* grouped-but-not-depthwise convs tile partially (classified separately);
* the network STEM (the conv consuming the raw ``in_chans``-channel input)
  is split out with its contraction depth ``K = kh·kw·cin`` and MXU lane
  occupancy ``K/128``: a 3-channel stem feeds 27 of 128 lanes, and the
  space-to-depth rewrite (``--stem-s2d``, ops/conv.py) is reclassified
  from the flag-built model's OWN jaxpr (2×2 kernel over 4C channels).

``obs/telemetry.py`` sums the buckets for ``forward_flops_per_sample``;
``tools/flops_breakdown.py`` prints them and the roofline ceilings.
"""

from __future__ import annotations

from collections import defaultdict

import numpy as np


def conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval          # kernel (H, W, Cin/g, Cout)
    # 2 * output elements * taps per output element
    kh, kw, cin_per_group, _ = rhs.shape
    return 2.0 * float(np.prod(out.shape)) * kh * kw * cin_per_group


def dot_flops(eqn) -> float:
    lhs = eqn.invars[0].aval
    out = eqn.outvars[0].aval
    ((lc, _), _) = eqn.params["dimension_numbers"]
    k = float(np.prod([lhs.shape[i] for i in lc]))
    return 2.0 * float(np.prod(out.shape)) * k


def analyze(model, variables, x, in_chans: int):
    """Placement buckets + the quantities the roofline needs.

    Returns ``(buckets, stem, dw_out_elems)``: FLOPs per class; stem
    diagnostics (kernel, contraction depth K, lane occupancy, flops) for
    the conv(s) consuming the raw ``in_chans``-channel input (4·in_chans
    when the model was built with ``stem_s2d``); and the total output
    element count of the depthwise convs (operand of the unfused-epilogue
    HBM term).
    """
    import jax

    jaxpr = jax.make_jaxpr(
        lambda v, x: model.apply(v, x, training=False))(variables, x)
    buckets = defaultdict(float)
    stem = {"flops": 0.0, "convs": []}
    stem_chans = (in_chans, 4 * in_chans)   # raw or space-to-depth input
    dw_out_elems = 0.0

    def walk(jx):
        nonlocal dw_out_elems
        for eqn in jx.eqns:
            for sub in (v for v in eqn.params.values()
                        if hasattr(v, "jaxpr")):
                walk(sub.jaxpr)
            if eqn.primitive.name == "conv_general_dilated":
                g = eqn.params["feature_group_count"]
                cin = eqn.invars[0].aval.shape[-1]
                f = conv_flops(eqn)
                if g == 1 and cin in stem_chans and not stem["convs"]:
                    kh, kw, _, _ = eqn.invars[1].aval.shape
                    k_depth = kh * kw * cin
                    buckets["conv_stem_mxu"] += f
                    stem["flops"] += f
                    stem["convs"].append({
                        "kernel": f"{kh}x{kw}x{cin}",
                        "contraction_depth": k_depth,
                        "mxu_lane_occupancy": round(min(1.0, k_depth / 128.0),
                                                    4),
                    })
                elif g == 1:
                    buckets["conv_dense_mxu"] += f
                elif g == cin:
                    buckets["conv_depthwise_vpu"] += f
                    dw_out_elems += float(np.prod(eqn.outvars[0].aval.shape))
                else:
                    buckets["conv_grouped_partial"] += f
            elif eqn.primitive.name == "dot_general":
                buckets["dot_mxu"] += dot_flops(eqn)

    walk(jaxpr.jaxpr)
    return dict(buckets), stem, dw_out_elems
