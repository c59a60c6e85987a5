"""Share (%) of the G, d, S and P elements each Eq. 19 round streams that
padding adds: 1 − the elements at the deployment's logical shapes
(Σ_j 2·D_j² + D_j + Σ_{p∈N(j)} D_j·D_p) over those of the packed shape
the program's `solve.batched` spans state (`nodes` J, `slots` K,
`d_max`: J·((2 + K)·D_max² + D_max)), averaged over the window's solves.
0 for equal widths; None when no `solve.batched` span of the window
states its shape (an older program)."""

SHAPE = ("nodes", "slots", "d_max")


def read(view):
    spans = [s.attrs for s in view.program_spans
             if s.name == "solve.batched"]
    if not spans or not all(set(SHAPE) <= set(a) for a in spans):
        return None
    dep = view.state["dep"]
    w = dep.widths
    logical = sum(2 * w[j] ** 2 + w[j] + sum(w[j] * w[p]
                                             for p in dep.neighbors(j))
                  for j in range(dep.num_nodes))
    padded = sum(a["nodes"] * ((2 + a["slots"]) * a["d_max"] ** 2
                               + a["d_max"]) for a in spans)
    return 100.0 * (1.0 - len(spans) * logical / padded)
