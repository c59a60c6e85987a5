"""MB (1e6 bytes) per fit that `pack_problem` copies host to device: the
program's `pack.h2d_bytes` count over every numpy array it uploads
(Gram pass, Eq. 17 build, finish)."""
from bench import program_trace as pt


def read(view):
    fits = view.result["counts"]["fits"]
    n = pt.total(view.trace, pt.events(view), "pack.h2d_bytes")
    return n / fits / 1e6 if fits and n is not None else None
