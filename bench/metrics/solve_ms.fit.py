"""Mean host time (ms) of `solve_batched` to tol in a fit: the
benchmark's span around it, ending on the device."""
from bench.trace import mean_span_ms


def read(view):
    return mean_span_ms(view.trace, "bench.solve")
