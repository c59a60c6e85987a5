"""Device ops per fit that the program's `ddrf.select` spans put on the
chip: ops that start from a fit's first `ddrf.select` to the end of the
benchmark's DDRF phase, which waits for the device. What one fit's
feature selection costs in device ops, plus any op of the fit's key
derivation, dispatched just before the phase, that the device starts
after the first selection has begun (a few a fit)."""
from bench import program_trace as pt


def read(view):
    fits = view.result["counts"]["fits"]
    spans = pt.dispatched_by(view.trace, pt.events(view), "ddrf.select",
                             "ddrf")
    if not fits or not spans:
        return None
    return pt.ops_starting_in(view.trace, view.devices[0], spans) / fits
