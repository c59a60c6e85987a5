"""Device-busy time (ms) per fit of the DDRF feature selection: from the
start of the program's first `ddrf.select` span of a fit (one per node,
`core/ddrf.py` `select_features`) to the end of the benchmark's DDRF
phase, which waits for the device, on the profiler's clock (with any op
of the fit's key derivation still queued when the first selection
starts). How much of DDRF's host time the chip works."""
from bench import program_trace as pt


def read(view):
    fits = view.result["counts"]["fits"]
    spans = pt.dispatched_by(view.trace, pt.events(view), "ddrf.select",
                             "ddrf")
    if not fits or not spans:
        return None
    return pt.busy_in(view.trace, view.devices[0], spans) / fits / 1e6
