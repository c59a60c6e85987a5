"""Share (%) of the serving roofline: the least time the chip could take
to featurize and answer the window's answered queries at logical shapes
(`bench.roofline.serve_work`), over the device-busy time in the window."""
from bench import roofline
from bench.trace import busy_ns


def read(view):
    lo, hi = view.trace.window()
    busy = busy_ns(view.trace, view.devices[0], lo, hi)
    answered = view.result["counts"]["answered"]
    if busy <= 0 or not answered:
        return None
    dep = view.state["dep"]
    flops, nbytes = roofline.serve_work(dep.widths, dep.dim, answered)
    least, _ = roofline.least_seconds(flops, nbytes, view.peak)
    return 100.0 * least / (busy / 1e9)
