"""Reduce a profiler trace to what the per-layer metrics read.

A traced run records the window with `jax.profiler.trace`. The benchmark's
own host spans are `jax.profiler.TraceAnnotation`s, so they sit in the
same trace, on the same clock, as the device's operations. From the
trace this module takes:

  * device ops: per device, (name, start_ns, end_ns) of every operation
    that ran on it (the device plane's "XLA Ops" line), named by the HLO
    instruction (the text before " = " of the event's HLO line);
  * async ops: the same for the "Async XLA Ops" line (copies and async
    collectives in flight), read only for collective time;
  * host spans: (name, start_ns, end_ns, attrs) of the annotations whose
    name starts with "bench.".

and computes the busy union, idle share, device time inside host spans,
the time of collective ops, the ops that took most time and the longest
idle gaps with what the host was doing in them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PREFIX = "bench."
# HLO collectives as they name their ops on the device.
_COLLECTIVE = re.compile(
    r"(collective-permute|all-gather|all-reduce|reduce-scatter|all-to-all"
    r"|collective-broadcast|ppermute|psum|pmax)", re.IGNORECASE)


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[tuple[str, int, int]]]        # device -> ops
    spans: list[tuple[str, int, int, dict]]           # bench host spans
    async_ops: dict[str, list[tuple[str, int, int]]] = dataclasses.field(
        default_factory=dict)                         # device -> async ops

    def span_intervals(self, name: str) -> list[tuple[int, int]]:
        return [(s, e) for n, s, e, _ in self.spans if n == name]

    def window(self) -> tuple[int, int]:
        """The traced window: the "bench.window" span."""
        w = self.span_intervals(HOST_PREFIX + "window")
        if not w:
            raise ValueError("the trace has no bench.window span")
        return w[0]


def op_name(event_name: str) -> str:
    """"%fusion.3 = f32[...] fusion(...)" -> "fusion.3"."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def profiler_options():
    """Device and host tracing with the Python tracer off: the bench
    spans are TraceMe annotations, which the host tracer records, and
    tracing every Python call would slow the host path under test."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def load(trace_dir: str) -> Trace:
    """Read the newest .xplane.pb under `trace_dir`."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops: dict[str, list[tuple[str, int, int]]] = {}
    async_ops: dict[str, list[tuple[str, int, int]]] = {}
    spans: list[tuple[str, int, int, dict]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {OPS_LINE: ops, ASYNC_LINE: async_ops}.get(line.name)
                if into is None:
                    continue
                into.setdefault(plane.name, []).extend(
                    (op_name(e.name), int(e.start_ns),
                     int(e.start_ns + e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns),
                                      dict(e.stats)))
    if not ops:
        seen = {pl.name: [ln.name for ln in pl.lines] for pl in data.planes}
        raise ValueError(f"no device ops in the trace; planes and lines: "
                         f"{seen}")
    return Trace(ops=ops, spans=sorted(spans, key=lambda s: s[1]),
                 async_ops=async_ops)


def merge(intervals) -> list[tuple[int, int]]:
    """Union of [start, end) intervals, as sorted disjoint intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged, lo: int, hi: int) -> int:
    """Length of the part of disjoint `merged` intervals inside [lo, hi)."""
    total = 0
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        total += min(e, hi) - max(s, lo)
    return total


def busy_ns(tr: Trace, device: str, lo: int, hi: int) -> int:
    return overlap(merge((s, e) for _, s, e in tr.ops.get(device, ())),
                   lo, hi)


def busy_in_spans(tr: Trace, device: str, name: str) -> int:
    """Device-busy ns inside every host span called `name`."""
    merged = merge((s, e) for _, s, e in tr.ops.get(device, ()))
    return sum(overlap(merged, s, e) for s, e in tr.span_intervals(name))


def idle_share(tr: Trace, devices=None) -> float:
    """1 − busy/window over the traced window, averaged over devices (%)."""
    lo, hi = tr.window()
    devices = list(tr.ops) if devices is None else devices
    if not devices or hi <= lo:
        raise ValueError("no device ops or an empty window")
    shares = [1.0 - busy_ns(tr, d, lo, hi) / (hi - lo) for d in devices]
    return 100.0 * sum(shares) / len(shares)


def is_collective(name: str) -> bool:
    return _COLLECTIVE.search(name) is not None


def collective_ns(tr: Trace, device: str) -> int:
    """ns inside the window during which a collective op, synchronous or
    in flight, ran on `device`."""
    lo, hi = tr.window()
    events = list(tr.ops.get(device, ())) + list(tr.async_ops.get(device, ()))
    return overlap(merge((s, e) for n, s, e in events if is_collective(n)),
                   lo, hi)


def top_ops(tr: Trace, n: int = 10) -> list[list]:
    """[[op name, seconds]] of the ops that took most device time inside
    the window, summed over devices and averaged per device."""
    lo, hi = tr.window()
    per: dict[str, int] = {}
    for ops in tr.ops.values():
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per[name] = per.get(name, 0) + d
    ndev = max(len(tr.ops), 1)
    ranked = sorted(per.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / ndev / 1e9] for name, ns in ranked]


def idle_gaps(tr: Trace, n: int = 10) -> list[list]:
    """[[host activity, seconds]] of the longest device-idle gaps inside
    the window (first device), each named by the innermost bench span
    that covers the gap's midpoint ("bench.window" when no phase does)."""
    lo, hi = tr.window()
    dev = sorted(tr.ops)[0]
    merged = [(max(s, lo), min(e, hi))
              for s, e in merge((s, e) for _, s, e in tr.ops[dev])
              if e > lo and s < hi]
    gaps, cursor = [], lo
    for s, e in merged:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        covering = [sp for sp in tr.spans if sp[1] <= mid < sp[2]]
        label = min(covering, key=lambda sp: sp[2] - sp[1])[0] \
            if covering else "outside bench spans"
        out.append([label, (e - s) / 1e9])
    return out


def mean_span_ms(tr: Trace, name: str):
    """Mean duration (ms) of the host spans called `name`, None if none."""
    spans = tr.span_intervals(name)
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) / 1e6
