"""Share (%) of the Eq. 17 build's work that padding adds: 1 − the work
at the deployment's logical shapes (each node's own D_j, N_j and
neighbours; `bench.roofline.eq17_work`) over the work at the shapes the
program padded to, summed over its `pack.stage` spans (`nodes` J,
`slots` K, `d_max`, `n_max`: every node at D_max and N_max, with K
neighbour slots). 0 for equal shards and widths; None when no
`pack.stage` span of the window states its shape (an older program)."""
from bench import roofline

SHAPE = ("nodes", "slots", "d_max", "n_max")


def padded_work(attrs: dict, dim: int) -> float:
    """Eq. 17 build flops at one `pack.stage` span's padded shape."""
    j, k = attrs["nodes"], attrs["slots"]
    flops, _ = roofline.eq17_work(
        [attrs["d_max"]] * j, [attrs["n_max"]] * j, dim,
        [[(i + 1 + s) % j for s in range(k)] for i in range(j)])
    return flops


def read(view):
    fits = view.result["counts"]["fits"]
    spans = [s.attrs for s in view.program_spans if s.name == "pack.stage"]
    if not fits or not spans or not all(set(SHAPE) <= set(a) for a in spans):
        return None
    dep = view.state["dep"]
    logical, _ = roofline.eq17_work(
        dep.widths, [x.shape[1] for x in dep.x_train], dep.dim,
        [dep.neighbors(j) for j in range(dep.num_nodes)])
    padded = sum(padded_work(a, dep.dim) for a in spans)
    return 100.0 * (1.0 - fits * logical / padded)
