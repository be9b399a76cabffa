"""Roofline share of the routed experts' grouped products of a training
step: the least time the chip could take for the assignments **the
program's counters report** (``lib/flops_moe.py:
experts_train_floor_seconds``: three forward passes of their operations at
peak, or the held experts' weights and the gathered rows at the memory's
rate, the larger), over the device time of the trace's operations in the
group ``moe_experts``.  The same work whatever implements it.

The assignments a routed token brought to the experts held are the rise of
``moe_assignments_total`` over that of ``moe_routed_tokens_total`` from the
window's snapshot to the trace's (the trainer drains at step 0 of every
epoch, so the rise is of whole steps), or the totals themselves where
nothing rose between the two; times the routed tokens of the traced rows.
Nothing where the program has no such counters (the parent), the trace no
such group or the counts are of another family."""

from benchmark.lib import flops_moe as F

TOKENS, ASSIGNED = "moe_routed_tokens_total", "moe_assignments_total"


def assignments_per_token(c0, c1):
    """Held assignments a routed token, from two snapshots of the counters;
    None where the program counted no routed token."""
    if not c1 or TOKENS not in c1 or ASSIGNED not in c1:
        return None
    c0 = c0 or {}
    tokens = c1[TOKENS] - c0.get(TOKENS, 0.0)
    assigned = c1[ASSIGNED] - c0.get(ASSIGNED, 0.0)
    if tokens <= 0:
        tokens, assigned = c1[TOKENS], c1[ASSIGNED]
    return assigned / tokens if tokens > 0 else None


def read(evidence, group="moe_experts", **_):
    red, traced, peak = (evidence.get("trace"), evidence.get("traced"),
                         evidence.get("peak"))
    counts = evidence.get("flop_counts") or {}
    share = assignments_per_token(evidence.get("counters0"),
                                  evidence.get("counters1"))
    if not red or not traced or not peak or share is None \
            or "moe_assignment_flops" not in counts:
        return None
    t = red["by_group"].get(group, 0.0)
    if t <= 0:
        return None
    floor = F.experts_train_floor_seconds(
        counts, share * counts["moe_tokens"] * traced["rows"],
        red.get("steps", 0), peak)
    return 100.0 * floor["seconds"] / t
