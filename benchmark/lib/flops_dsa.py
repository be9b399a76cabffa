"""Operations of one row through a stack of learned-sparse-attention layers
with routed experts held in part, counted from shapes by the benchmark.

The conventions are ``lib/flops_seq.py``'s: a matrix product is 2 x
positions x in x out, a training step is three forward passes (what a
metric multiplies by 3), recomputed work is not counted, and norms, the
rotation, the gates and the selection itself (no product: the MXU does
none of it) are left out.  Two groups differ, so that **the total is a
third of the step's needed training operations** and ``step_mfu.train``'s
three passes stay right:

* ``dsa_index``: the indexer's projections (d -> 16 x 64, d -> 64, d ->
  16) do a forward and a weight gradient and no input gradient (their input
  is held): two passes, counted as 2/3 of three.  Its scores
  (``2 x heads x width`` a pair) are needed forward over **every causal
  pair**, the selection has to see them all: causal / 3 of three passes.
  These are the products whose time is ``dsa_index`` + ``dsa_select``;
* ``attn_sparse``: the attention over **the selected pairs only** (a pair
  costs, a query head, 2 x 128 for the score and 2 x 128 for the value),
  ``min(t + 1, topk)`` keys a query, and the index scores' backward for
  the indexer's loss over the selected pairs only (2 x selected / 3 of
  three passes of a score), which runs in the attention's backward
  kernels; ``dsa_kl``'s work is elementwise;
* ``attn_proj``: q, k, v and the output projection;
* ``moe_router``, ``head``: their products; ``moe_experts`` at **the uniform
  share**: every routed token brings ``top_k x held / experts`` assignments
  to the experts held here, each 2 x 3 x d x f operations, as
  ``lib/flops_moe.py`` counts them, with its ``moe_tokens``,
  ``moe_assignment_flops``, ``moe_weight_elems`` and ``moe_row_elems``
  beside them for ``metrics/moe_roofline.py``.

``selected_pairs`` and ``causal_pairs`` of a row are given beside them.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.lib.flops_seq import causal_pairs

GROUPS = ("attn_proj", "dsa_index", "attn_sparse", "moe_router",
          "moe_experts", "head")
_NOT_FLOPS = ("moe_tokens", "moe_assignment_flops", "moe_weight_elems",
              "moe_row_elems", "selected_pairs", "causal_pairs")


def selected_pairs(l: int, topk: int) -> int:
    """sum over the positions t of min(t + 1, topk)."""
    k = min(topk, l)
    return k * (k + 1) // 2 + (l - k) * k


def counts_for(spec: Dict[str, Any], l: int) -> Dict[str, float]:
    """One row of ``l`` positions; ``spec`` as
    ``reference/keyevl2.py:model_spec`` gives it."""
    d, h, hk, dh = spec["d"], spec["heads"], spec["kv_heads"], spec["dh"]
    nj, e, f = spec["index_heads"], spec["index_dim"], spec["f"]
    held, share = spec["held"][1], spec["held"][1] / spec["experts"]
    mm = lambda i, o: 2.0 * l * i * o                       # noqa: E731
    pairs, picked = causal_pairs(l), selected_pairs(l, spec["topk"])
    acc = {k: 0.0 for k in GROUPS + _NOT_FLOPS}
    acc["moe_assignment_flops"] = 2.0 * 3 * d * f
    acc["moe_row_elems"] = 2.0 * d
    acc["selected_pairs"] = float(picked)
    acc["causal_pairs"] = float(pairs)
    score = 2.0 * nj * e
    for _ in range(spec["layers"]):
        acc["attn_proj"] += mm(d, h * dh) + 2 * mm(d, hk * dh) \
            + mm(h * dh, d)
        acc["dsa_index"] += 2.0 / 3.0 * (mm(d, nj * e) + mm(d, e)
                                         + mm(d, nj)) + score * pairs / 3.0
        acc["attn_sparse"] += 4.0 * dh * h * picked \
            + score * 2.0 * picked / 3.0
        acc["moe_router"] += mm(d, spec["experts"])
        acc["moe_experts"] += l * spec["top_k"] * share \
            * acc["moe_assignment_flops"]
        acc["moe_tokens"] += l
        acc["moe_weight_elems"] += held * 3.0 * d * f
    acc["head"] = mm(d, spec["rows"])
    acc["forward_flops"] = sum(acc[k] for k in GROUPS)
    return acc
