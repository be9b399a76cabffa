"""Operations of one row through a stack of latent-attention layers, dense
MLPs and shared-plus-routed expert layers held in part, counted from shapes
by the benchmark.

The conventions are ``lib/flops_seq.py``'s: a matrix product is 2 x positions
x in x out; attention counts **the unmasked pairs only** (a causal layer has
L(L+1)/2 (query, key) pairs; a pair costs, a query head, 2 x the score's
width -- the 192 channels of its own key and the 64 of the shared rotary
key -- and 2 x the value's width); a training step is three forward passes;
recomputed work is not counted.  Norms, the rotation and the gates are left
out.

* ``mla_proj``: the down projections of q (d -> 768) and of the key / value
  latent with the rotary key (d -> 512 + 64), the up projections (768 ->
  heads x 256, 512 -> heads x (192 + 256)) and the output projection (heads
  x 256 -> d);
* ``attn_latent``: the pairs;
* ``mlp_dense``, ``moe_shared``, ``moe_router``, ``head``: their products;
* ``moe_experts`` at **the uniform share**: every routed token brings
  ``top_k x held / experts`` assignments to the experts held here, each 2 x
  3 x d x f operations, as ``lib/flops_moe.py`` counts them, with its
  ``moe_tokens``, ``moe_assignment_flops``, ``moe_weight_elems`` and
  ``moe_row_elems`` beside them for ``metrics/moe_roofline.py``.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.lib.flops_seq import causal_pairs

GROUPS = ("mla_proj", "attn_latent", "mlp_dense", "moe_shared", "moe_router",
          "moe_experts", "head")
_NOT_FLOPS = ("moe_tokens", "moe_assignment_flops", "moe_weight_elems",
              "moe_row_elems")


def counts_for(spec: Dict[str, Any], layers, l: int) -> Dict[str, float]:
    """One row of ``l`` positions through ``layers`` (dense FFN? each);
    ``spec`` as ``reference/glm47flash.py:model_spec`` gives it."""
    d, h, f = spec["d"], spec["heads"], spec["f"]
    dn, dr, dv = spec["nope"], spec["rope"], spec["dv"]
    held, share = spec["held"][1], spec["held"][1] / spec["experts"]
    mm = lambda i, o: 2.0 * l * i * o                       # noqa: E731
    acc = {k: 0.0 for k in GROUPS + _NOT_FLOPS}
    acc["moe_assignment_flops"] = 2.0 * 3 * d * f
    acc["moe_row_elems"] = 2.0 * d
    for dense in layers:
        acc["mla_proj"] += mm(d, spec["q_rank"]) \
            + mm(spec["q_rank"], h * (dn + dr)) \
            + mm(d, spec["kv_rank"] + dr) \
            + mm(spec["kv_rank"], h * (dn + dv)) + mm(h * dv, d)
        acc["attn_latent"] += (2.0 * (dn + dr) + 2.0 * dv) * h \
            * causal_pairs(l)
        if dense:
            acc["mlp_dense"] += mm(d, 2 * spec["ff"]) + mm(spec["ff"], d)
        else:
            acc["moe_shared"] += mm(d, 2 * spec["shared"]) \
                + mm(spec["shared"], d)
            acc["moe_router"] += mm(d, spec["experts"])
            acc["moe_experts"] += l * spec["top_k"] * share \
                * acc["moe_assignment_flops"]
            acc["moe_tokens"] += l
            acc["moe_weight_elems"] += held * 3.0 * d * f
    acc["head"] = mm(d, spec["rows"])
    acc["forward_flops"] = sum(acc[k] for k in GROUPS)
    return acc
