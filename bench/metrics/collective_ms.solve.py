"""Device time (ms) of collective ops (collective-permute, all-gather,
all-reduce, ...) per SPMD solve, averaged over the chips the cell uses."""
from bench.trace import collective_ns


def read(view):
    solves = view.result["counts"]["solves"]
    if not solves:
        return None
    per_chip = [collective_ns(view.trace, d) for d in view.devices]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / solves / 1e6
