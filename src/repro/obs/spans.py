"""Host-side trace spans and counters: nested wall-clock intervals
around the runtimes' staging work (`pack_problem` and its `pack.stage` /
`pack.gram`, the `solve.batched` dispatch, `ddrf.select`, stream
ingest/refresh/publish, serve waves).

Spans measure *host* work — tracing/compile/staging/queueing — never the
device-side solve rounds (those are the on-device `return_trace=`
buffers; see the package docstring). Instrumented library code calls

    with span("pack_problem", nodes=j):
        ...

which is a no-op (one attribute read) unless a `SpanRecorder` is
installed. The harness that wants spans installs one for the duration
of a run:

    with recording(registry) as rec:
        ... run benches / serve ...
    # finished spans are now in registry.spans

Nesting is tracked per thread (each replica thread gets its own depth
stack against the one installed recorder), so a serve-wave span inside
a bench-suite span renders as an indented waterfall in the report CLI.

Counters sit at the same boundaries: `count(name, n)` adds to the
installed recorder's `counts` and is a no-op without one. A call site
whose count costs work to compute (summing `nbytes`) asks
`is_recording()` first.

An installed recorder also puts the program on the profiler's timeline,
so that a profiler trace taken meanwhile holds it on the same clock as
the device's ops: each span as `jax.profiler.TraceAnnotation(
TIMELINE_PREFIX + name, **attrs)`, each count as a zero-length
annotation `COUNT_PREFIX + name` whose `n` stat is the amount. With no
profiler running an annotation costs next to nothing. `recording()` also
counts each executable JAX compiles or loads from its compilation cache
as `jax.compiles`. jax is imported only then: this module imports
without it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Iterator

import numpy as np

from repro.obs.metrics import Registry, perf_clock

__all__ = ["COMPILES", "COUNT_PREFIX", "Span", "SpanRecorder",
           "TIMELINE_PREFIX", "count", "h2d_nbytes", "install",
           "is_recording", "recording", "span", "uninstall"]

# Name prefix of the program's spans on the profiler's timeline.
TIMELINE_PREFIX = "repro."
# Name prefix of the program's counts on the profiler's timeline.
COUNT_PREFIX = TIMELINE_PREFIX + "count."
# The counter of executables compiled or loaded from the compilation cache.
COMPILES = "jax.compiles"
# JAX's monitoring event around every compile, cache hits included.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class Span:
    """One finished interval. `depth` is the nesting level within its
    thread (0 = top-level); `parent` is the enclosing span's name."""

    name: str
    t_start: float
    t_end: float
    depth: int
    parent: str | None
    thread: str
    attrs: dict[str, Any]

    @property
    def duration(self) -> float:
        return self.t_end - self.t_start


class SpanRecorder:
    """Collects finished spans and counts, and puts both on the
    profiler's timeline (module docstring); optionally forwards the spans
    to a `Registry` (the exporters read `registry.spans`)."""

    def __init__(self, clock: Callable[[], float] = perf_clock,
                 registry: Registry | None = None):
        self.clock = clock
        self.registry = registry
        self._lock = threading.Lock()
        self._local = threading.local()
        self.spans: list[Span] = []
        self.counts: dict[str, float] = {}

    def add(self, name: str, n: float = 1) -> None:
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + n
        import jax

        with jax.profiler.TraceAnnotation(COUNT_PREFIX + name, n=n):
            pass

    def _stack(self) -> list[str]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[None]:
        stack = self._stack()
        depth = len(stack)
        parent = stack[-1] if stack else None
        stack.append(name)
        t0 = self.clock()
        try:
            import jax

            with jax.profiler.TraceAnnotation(TIMELINE_PREFIX + name,
                                              **attrs):
                yield
        finally:
            t1 = self.clock()
            stack.pop()
            sp = Span(name=name, t_start=float(t0), t_end=float(t1),
                      depth=depth, parent=parent,
                      thread=threading.current_thread().name,
                      attrs=dict(attrs))
            with self._lock:
                self.spans.append(sp)
            if self.registry is not None:
                self.registry.record_span(sp)


# The process-wide installed recorder. Library call sites are always-on
# cheap: `span()` reads this once and yields immediately when None.
_installed: SpanRecorder | None = None
_install_lock = threading.Lock()


def install(recorder: SpanRecorder) -> SpanRecorder:
    """Make `recorder` the process-wide span sink (replaces any prior)."""
    global _installed
    with _install_lock:
        _installed = recorder
    return recorder


def uninstall() -> None:
    global _installed
    with _install_lock:
        _installed = None


def _listen_for_compiles(rec: SpanRecorder) -> Callable[..., None]:
    """Count into `rec` every executable JAX compiles or loads from its
    cache (one `_COMPILE_EVENT` each); returns the registered listener."""
    import jax.monitoring

    rec.counts[COMPILES] = 0

    def on_duration(event: str, duration_secs: float, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            rec.add(COMPILES)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return on_duration


@contextlib.contextmanager
def recording(registry: Registry | None = None,
              clock: Callable[[], float] = perf_clock
              ) -> Iterator[SpanRecorder]:
    """Install a fresh recorder for the scope, restore the prior one
    after — the harness-side entry point. It also counts compiles for the
    scope (module docstring)."""
    rec = SpanRecorder(clock=clock, registry=registry)
    listener = _listen_for_compiles(rec)
    with _install_lock:
        global _installed
        prev, _installed = _installed, rec
    try:
        yield rec
    finally:
        with _install_lock:
            _installed = prev
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(listener)


def is_recording() -> bool:
    """Whether a recorder is installed: call sites whose count costs work
    to compute ask this first."""
    return _installed is not None


def count(name: str, n: float = 1) -> None:
    """Library-side counter: adds `n` to the installed recorder's
    `counts[name]`, no-op when none is installed."""
    rec = _installed
    if rec is not None:
        rec.add(name, n)


def h2d_nbytes(*arrays: Any) -> int:
    """Bytes `jnp.asarray` copies to the device for the numpy arrays among
    `arrays`, at the dtype JAX gives them (64-bit ones are cast to 32 bits
    unless x64 is on); device arrays copy nothing."""
    from jax import dtypes

    return sum(a.size * np.dtype(dtypes.canonicalize_dtype(a.dtype)).itemsize
               for a in arrays if isinstance(a, np.ndarray))


@contextlib.contextmanager
def span(name: str, **attrs: Any) -> Iterator[None]:
    """Library-side span: records into the installed recorder, no-op
    when none is installed."""
    rec = _installed
    if rec is None:
        yield
        return
    with rec.span(name, **attrs):
        yield
