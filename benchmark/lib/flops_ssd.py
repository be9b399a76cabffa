"""Operations and bytes of one row through a stack of Mamba-2 (state-space
dual) and grouped-attention layers, counted from shapes by the benchmark.

The conventions are ``lib/flops_seq.py``'s: a matrix product is 2 x positions
x in x out; attention counts **the unmasked pairs only** (a causal layer has
L(L+1)/2 (query, key) pairs; a pair costs 2 x head size for the score and 2 x
head size for the values, a query head); a training step is three forward
passes; recomputed work is not counted.

The scan is counted as the chunked dual form states it, whichever form of
``ops/ssd.py`` runs: inside a chunk of Q positions the unmasked (t, r <= t)
pairs cost 2 x N once for ``C B^T`` (shared by the heads) and 2 x H x P for
the product with ``x``; every position costs 2 x H x P x N for the chunk's
own state and as much for the readout of the state it started from.  The
convolution's four taps and the skip are left out (0.02% of a layer).  Its
bytes are what a training step has to move for one scan: forward reads x, B,
C, dt and writes y; backward reads x, B, C, dt, dy and writes the four
gradients.

``forward_flops`` feeds ``step_mfu.train`` as that reader expects; the
per-group entries and ``ssd_elems`` feed the rooflines.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.lib.flops_seq import causal_pairs

MAMBA, ATTENTION = "mamba", "attention"


def chunk_pairs(l: int, q: int) -> int:
    """(t, r <= t) pairs of one row inside its chunks of ``q`` positions."""
    return (l // q) * causal_pairs(q) + causal_pairs(l % q)


def counts_for(spec: Dict[str, Any], kinds, l: int) -> Dict[str, float]:
    """One row of ``l`` positions through layers of the given ``kinds``;
    ``spec`` as ``reference/granite4h.py:model_spec`` gives it."""
    d, ff, inner, n = spec["d"], spec["ff"], spec["inner"], spec["n"]
    h, hk, dh = spec["heads"], spec["kv_heads"], spec["dh"]
    sh = spec["ssm_heads"]
    mm = lambda i, o: 2.0 * l * i * o                       # noqa: E731
    acc = {k: 0.0 for k in ("ssd_proj", "ssd_scan", "attn_full", "mlp",
                            "head", "ssd_elems")}
    for kind in kinds:
        acc["mlp"] += mm(d, 2 * ff) + mm(ff, d)
        if kind == MAMBA:
            acc["ssd_proj"] += mm(d, 2 * inner + 2 * n + sh) + mm(inner, d)
            acc["ssd_scan"] += chunk_pairs(l, spec["chunk"]) \
                * (2.0 * n + 2.0 * inner) + 2 * (2.0 * l * inner * n)
            acc["ssd_elems"] += l * (2.0 * (2 * inner + 2 * n + sh)
                                     + inner + 2 * n + sh)
        else:
            acc["attn_full"] += mm(d, (h + 2 * hk) * dh) + mm(h * dh, d) \
                + (2.0 * dh + 2.0 * dh) * h * causal_pairs(l)
    acc["head"] = mm(d, spec["rows"])
    acc["forward_flops"] = sum(v for k, v in acc.items() if k != "ssd_elems")
    return acc


def scan_train_floor_seconds(counts, rows: float, peak,
                             bytes_per_elem: int = 2) -> Dict[str, float]:
    """The least time the chip could take for the scans of ``rows`` rows of
    a training step: the larger of three forward passes of their operations
    at peak and their bytes (two an element, the configuration's compute
    dtype) at the memory's rate."""
    t_flops = 3.0 * counts["ssd_scan"] * rows / peak["bf16_flops_per_s"]
    t_bytes = counts["ssd_elems"] * rows * bytes_per_elem \
        / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "t_flops": t_flops,
            "t_bytes": t_bytes,
            "bound": "bytes" if t_bytes >= t_flops else "flops"}
