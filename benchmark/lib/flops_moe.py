"""Operations and bytes of one row through a stack of gated short
convolutions, grouped-attention layers, dense MLPs and routed expert layers
held in part, counted from shapes by the benchmark.

The conventions are ``lib/flops_seq.py``'s: a matrix product is 2 x positions
x in x out; attention counts **the unmasked pairs only** (a causal layer has
L(L+1)/2 (query, key) pairs; a pair costs 2 x head size for the score and 2 x
head size for the values, a query head); a training step is three forward
passes; recomputed work is not counted.  Norms, the rotation, the
convolution's three taps and the gates are left out (under 0.1% of a layer).

The expert layer's work depends on the routing that ran.  ``moe_experts`` in
``forward_flops`` (which feeds ``step_mfu.train``) is counted **at the
uniform share**: every routed token brings ``top_k x held / experts``
assignments to the experts held here, each 2 x 3 x d x f operations (the
SwiGLU's three products).  The share that ran is a counter of the program
(``moe_assignments_total`` over ``moe_routed_tokens_total``) and stands
beside it as the metric ``moe_assignments_per_token.train``;
``metrics/moe_roofline.py`` prices the grouped products by the counters and
not by this figure.

``moe_tokens`` (routed tokens a row: positions x expert layers),
``moe_assignment_flops``, ``moe_weight_elems`` (the held experts' weights,
all expert layers) and ``moe_row_elems`` (what one assignment gathers and
scatters: a row of width d in, one out) feed that reader.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.lib.flops_seq import causal_pairs

CONV, ATTENTION = "conv", "full_attention"
_NOT_FLOPS = ("moe_tokens", "moe_assignment_flops", "moe_weight_elems",
              "moe_row_elems")


def counts_for(spec: Dict[str, Any], layers, l: int) -> Dict[str, float]:
    """One row of ``l`` positions through ``layers`` ((mixer kind, dense
    FFN?) each); ``spec`` as ``reference/lfm2moe.py:model_spec`` gives it."""
    d, ff, f = spec["d"], spec["ff"], spec["f"]
    h, hk, dh = spec["heads"], spec["kv_heads"], spec["dh"]
    held, share = spec["held"][1], spec["held"][1] / spec["experts"]
    mm = lambda i, o: 2.0 * l * i * o                       # noqa: E731
    acc = {k: 0.0 for k in ("conv_mix", "attn_full", "mlp_dense",
                            "moe_router", "moe_experts", "head") + _NOT_FLOPS}
    acc["moe_assignment_flops"] = 2.0 * 3 * d * f
    acc["moe_row_elems"] = 2.0 * d
    for kind, dense in layers:
        if kind == CONV:
            acc["conv_mix"] += mm(d, 3 * d) + mm(d, d)
        else:
            acc["attn_full"] += mm(d, (h + 2 * hk) * dh) + mm(h * dh, d) \
                + (2.0 * dh + 2.0 * dh) * h * causal_pairs(l)
        if dense:
            acc["mlp_dense"] += mm(d, 2 * ff) + mm(ff, d)
        else:
            acc["moe_router"] += mm(d, spec["experts"])
            acc["moe_experts"] += l * spec["top_k"] * share \
                * acc["moe_assignment_flops"]
            acc["moe_tokens"] += l
            acc["moe_weight_elems"] += held * 3.0 * d * f
    acc["head"] = mm(d, spec["rows"])
    acc["forward_flops"] = sum(v for k, v in acc.items()
                               if k not in _NOT_FLOPS)
    return acc


def experts_train_floor_seconds(counts, assignments: float, steps: float,
                                peak, bytes_per_elem: int = 2
                                ) -> Dict[str, float]:
    """The least time the chip could take for the grouped products of
    ``assignments`` (token, held expert) assignments over ``steps`` training
    steps: the larger of three forward passes of their operations at peak
    and, at the memory's rate, three passes (forward, the rows' gradient,
    the weights') over the held experts' weights a step and the rows each
    assignment gathers and scatters (two bytes an element, the
    configuration's compute dtype)."""
    t_flops = 3.0 * assignments * counts["moe_assignment_flops"] \
        / peak["bf16_flops_per_s"]
    t_bytes = 3.0 * (steps * counts["moe_weight_elems"]
                     + assignments * counts["moe_row_elems"]) \
        * bytes_per_elem / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "t_flops": t_flops,
            "t_bytes": t_bytes,
            "bound": "bytes" if t_bytes >= t_flops else "flops"}
