"""Host time (ms) per fit of the program's own `pack.stage` span: the
numpy padding and neighbour gathers of `pack_problem`, before the Gram
pass."""


def read(view):
    fits = view.result["counts"]["fits"]
    spans = [s.duration for s in view.program_spans if s.name == "pack.stage"]
    return 1e3 * sum(spans) / fits if fits and spans else None
