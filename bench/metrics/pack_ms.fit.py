"""Mean host time (ms) of solver construction plus `pack_problem` in a
fit: the benchmark's span around them, ending on the device."""
from bench.trace import mean_span_ms


def read(view):
    return mean_span_ms(view.trace, "bench.pack")
