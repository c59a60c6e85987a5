"""Mean host time (ms) of the DDRF feature selection of a fit: the
benchmark's span around the per-node `select_features` calls, each phase
ending on the device in the traced run."""
from bench.trace import mean_span_ms


def read(view):
    return mean_span_ms(view.trace, "bench.ddrf")
