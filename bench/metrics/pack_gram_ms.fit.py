"""Host time (ms) per fit of the program's own `pack.gram` span: the
Pallas Gram pass of `pack_problem` (upload, kernel, readback)."""


def read(view):
    fits = view.result["counts"]["fits"]
    spans = [s.duration for s in view.program_spans if s.name == "pack.gram"]
    return 1e3 * sum(spans) / fits if fits and spans else None
