"""Operations and bytes of one row through a sequence configuration (the
SambaY stack), counted from shapes by the benchmark.

Projections, MLP and head count as ``lib/flops.py:walk`` counts a matrix
product: 2 x positions x in x out.  Attention counts **the unmasked pairs
only**: a causal layer has L(L+1)/2 (query, key) pairs, a window layer
sum_t min(t+1, window); each pair costs, per softmax map, 2 x head size for
the score and 2 x (2 x head size) for the 128-wide values, times two maps
and the query pairs.  (A count from a dense reference's dot shapes would
read the window layer L/window times too high, and a share of a peak above
100%.)  The recurrence's body counts six operations per (position, channel,
state) -- the decay's exponential, its product with the state, the input's
product and sum, the readout's product and sum -- and two per (position,
channel) for the skip: a ``scan`` body times its length.  A training step is
three forward passes, as for every cell; recomputed work is not counted.

``forward_flops`` feeds ``step_mfu.train`` as that reader expects; the
per-group entries and the recurrence's bytes feed the rooflines.
"""

from __future__ import annotations

from typing import Any, Dict

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


def causal_pairs(l: int) -> int:
    return l * (l + 1) // 2


def window_pairs(l: int, window: int) -> int:
    w = min(window, l)
    return w * (w + 1) // 2 + (l - w) * w


def counts_for(spec: Dict[str, Any], kinds, l: int) -> Dict[str, float]:
    """One row of ``l`` positions through layers of the given ``kinds``;
    ``spec`` as the reference's ``model_spec`` gives it."""
    d, ff, inner, n, r = (spec["d"], spec["ff"], spec["inner"], spec["n"],
                          spec["rank"])
    h, hk, dh = spec["heads"], spec["kv_heads"], spec["dh"]
    mm = lambda i, o: 2.0 * l * i * o                       # noqa: E731
    per_pair = (2.0 * dh + 2.0 * 2 * dh) * 2 * (h // 2)
    acc = {k: 0.0 for k in ("mamba_proj", "mamba_scan", "attn_window",
                            "attn_full", "attn_cross", "gmu", "mlp", "head",
                            "scan_elems")}
    for kind in kinds:
        acc["mlp"] += mm(d, 2 * ff) + mm(ff, d)
        if kind == MAMBA:
            acc["mamba_proj"] += mm(d, 2 * inner) + mm(inner, r + 2 * n) \
                + mm(r, inner) + mm(inner, d)
            acc["mamba_scan"] += 6.0 * l * inner * n + 2.0 * l * inner
            # elements a training step has to move for one scan: forward
            # reads u, delta, B, C and writes y; backward reads u, delta,
            # B, C and dy and writes the four gradients
            acc["scan_elems"] += 8.0 * l * inner + 6.0 * l * n
        elif kind == GMU:
            acc["gmu"] += mm(d, inner) + mm(inner, d)
        elif kind == CROSS:
            acc["attn_cross"] += mm(d, h * dh) + mm(h * dh, d) \
                + per_pair * causal_pairs(l)
        elif kind == FULL:
            acc["attn_full"] += mm(d, (h + 2 * hk) * dh) + mm(h * dh, d) \
                + per_pair * causal_pairs(l)
        elif kind == WINDOW:
            acc["attn_window"] += mm(d, (h + 2 * hk) * dh) + mm(h * dh, d) \
                + per_pair * window_pairs(l, spec["window"])
    acc["head"] = mm(d, spec["rows"])
    acc["forward_flops"] = sum(v for k, v in acc.items() if k != "scan_elems")
    return acc


def forward_counts(config: Dict[str, Any]) -> Dict[str, float]:
    """Counts for ONE row of the configuration's sequence length."""
    from benchmark import reference
    R = reference.model(config)
    spec = R.model_spec(config)
    return counts_for(spec, R.schedule(spec), int(config["train"]["seq_len"]))


def scan_train_floor_seconds(counts, rows: float, peak,
                             bytes_per_elem: int = 2) -> Dict[str, float]:
    """The least time the chip could take for the recurrences of ``rows``
    rows of a training step: bytes bind (two bytes an element, the
    configuration's compute dtype, against float32 arithmetic)."""
    t_flops = 3.0 * counts["mamba_scan"] * rows / peak["bf16_flops_per_s"]
    t_bytes = counts["scan_elems"] * rows * bytes_per_elem \
        / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "t_flops": t_flops,
            "t_bytes": t_bytes,
            "bound": "bytes" if t_bytes >= t_flops else "flops"}


def group_train_floor_seconds(counts, groups, rows: float, peak) -> float:
    """Three forward passes of the named groups' operations at peak."""
    return 3.0 * sum(counts[g] for g in groups) * rows \
        / peak["bf16_flops_per_s"]
