"""The program's own spans and counts on the profiler's timeline.

While the traced window runs, the program's span recorder puts each of
its spans on the profiler's timeline as an annotation named
"repro.<span>", and each count as a zero-length annotation
"repro.count.<counter>" whose `n` stat is the amount
(`repro.obs.spans`). `bench.trace` keeps only the benchmark's own
"bench." spans; this module reads the program's from the same trace
file, for the per-layer metrics that name them. A program that puts
nothing on the timeline (an older checkout) gives no events, and those
metrics then read None.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os

from bench import trace as btrace

PREFIX = "repro."               # repro.obs.spans.TIMELINE_PREFIX
COUNT_PREFIX = PREFIX + "count."  # repro.obs.spans.COUNT_PREFIX
TRACE_DIR = ".bench_trace"      # bench/run.py's TRACE_DIR, under the root


def events(view) -> list[tuple[str, int, int, dict]]:
    """(name, start_ns, end_ns, stats) of the program's host events in the
    trace of `view`'s run, by start; [] when there are none, or when the
    trace file is not the one `view.trace` was read from."""
    paths = sorted(glob.glob(os.path.join(view.cell.root, TRACE_DIR, "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return []
    st = os.stat(paths[-1])
    bench, program = _load(paths[-1], st.st_mtime_ns, st.st_size)
    return list(program) if bench == view.trace.spans else []


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int, size: int):
    """(bench spans, program events) of one trace file, each by start;
    the benchmark's spans as `bench.trace.load` reads them."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    bench, program = [], []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(btrace.HOST_PREFIX):
                    into = bench
                elif e.name.startswith(PREFIX):
                    into = program
                else:
                    continue
                into.append((e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns), dict(e.stats)))
    return (sorted(bench, key=lambda s: s[1]),
            tuple(sorted(program, key=lambda s: s[1])))


def intervals(program, name: str) -> list[tuple[int, int]]:
    """[start, end) of the program's span `name` (without the prefix)."""
    return [(s, e) for n, s, e, _ in program if n == PREFIX + name]


def total(tr: btrace.Trace, program, counter: str):
    """Sum of the program's `counter` inside the traced window; None when
    the program put nothing on the timeline."""
    if not program:
        return None
    lo, hi = tr.window()
    return sum(st.get("n", 0) for n, s, _, st in program
               if n == COUNT_PREFIX + counter and lo <= s < hi)


def dispatched_by(tr: btrace.Trace, program, name: str,
                  phase: str) -> list[tuple[int, int]]:
    """The device time that the program's `name` spans dispatched: for each
    benchmark span `phase` that holds some, from the first one's start to
    the phase's end. Dispatch is asynchronous, so a span's ops may run
    after it closes; the phase ends on the device in a traced run
    (`harness.Phases.sync`), so its end takes in the last op. Ops
    dispatched before the first span that start on the device after it
    count too: a reading is exact only up to them."""
    starts = [s for s, _ in intervals(program, name)]
    out = []
    for lo, hi in tr.span_intervals(btrace.HOST_PREFIX + phase):
        inside = [s for s in starts if lo <= s < hi]
        if inside:
            out.append((min(inside), hi))
    return out


def busy_in(tr: btrace.Trace, device: str, spans) -> int:
    """Device-busy ns inside the union of `spans`."""
    merged = btrace.merge((s, e) for _, s, e in tr.ops.get(device, ()))
    return sum(btrace.overlap(merged, s, e) for s, e in btrace.merge(spans))


def ops_starting_in(tr: btrace.Trace, device: str, spans) -> int:
    """Number of ops on `device` that start inside the union of `spans`."""
    spans = btrace.merge(spans)
    ends = [e for _, e in spans]
    n = 0
    for _, s, _ in tr.ops.get(device, ()):
        i = bisect.bisect_right(ends, s)        # first span ending after s
        if i < len(spans) and spans[i][0] <= s:
            n += 1
    return n
