"""MB (1e6 bytes) per fit that DDRF copies host to device: the program's
`ddrf.h2d_bytes` count, the numpy x and y each `select_features` call
takes."""
from bench import program_trace as pt


def read(view):
    fits = view.result["counts"]["fits"]
    n = pt.total(view.trace, pt.events(view), "ddrf.h2d_bytes")
    return n / fits / 1e6 if fits and n is not None else None
