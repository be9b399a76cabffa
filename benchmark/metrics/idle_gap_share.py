"""Share of the trace's longest idle gaps (``breakdown.idle_gaps``, at most
ten, each named by the host event that overlaps it most) whose name
contains ``contains``, by seconds: ``dfd.`` is every span the program
writes, ``dfd.input.host_wait`` the train loop waiting for the host
loader.  A program that writes no span reads 0.  Nothing where the trace
lists no gap."""


def read(evidence, contains, **_):
    gaps = (evidence.get("trace") or {}).get("idle_gaps")
    total = sum(s for _, s in gaps) if gaps else 0.0
    if total <= 0:
        return None
    return 100.0 * sum(s for name, s in gaps if contains in name) / total
