"""Share (%) of the Eq. 17 build's roofline: the least time the chip
could take for the work of building every node's G, d, S and P at the
deployment's logical shapes (larger of flops over peak and bytes over
peak bandwidth; `bench.roofline.eq17_work`), over the device-busy time
inside the benchmark's pack spans."""
from bench import roofline
from bench.trace import busy_in_spans


def read(view):
    dep = view.state["dep"]
    spans = view.trace.span_intervals("bench.pack")
    busy = busy_in_spans(view.trace, view.devices[0], "bench.pack")
    if not spans or busy <= 0:
        return None
    flops, nbytes = roofline.eq17_work(
        dep.widths, [x.shape[1] for x in dep.x_train], dep.dim,
        [dep.neighbors(j) for j in range(dep.num_nodes)])
    least, _ = roofline.least_seconds(flops, nbytes, view.peak)
    return 100.0 * least * len(spans) / (busy / 1e9)
