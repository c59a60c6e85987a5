"""Unified telemetry for every runtime: what is recorded where.

Architecture map
----------------

::

    ON DEVICE (zero host callbacks — J001; zero extra dispatches — J002)
      solve_batched / async_solve_batched / chebyshev_solve_packed /
      make_[async_]spmd_solver, all with `return_trace=True`
        └─▶ SolveTrace / AsyncSolveTrace  (repro.obs.trace)
            per-round max|Δθ| in a preallocated [R] carry inside the
            existing while/scan; async adds active / broadcasts /
            deliveries / bytes per round. Chunk-invariant, rtol-1e-9
            exact vs per-round recomputation (tests/test_obs.py).

    HOST SIDE (stdlib clocks, injectable — R006 chokepoint)
      pack_problem (pack.stage, pack.gram) · solve.batched dispatch ·
      ddrf.select · stream ingest/refresh/publish · serve waves
        └─▶ spans (repro.obs.spans: nested context-manager intervals,
            recorded only while a SpanRecorder is installed, and then
            also jax.profiler.TraceAnnotations named TIMELINE_PREFIX +
            name, on the device trace's clock)
      ddrf.h2d_bytes · pack.h2d_bytes (numpy bytes copied to the device)
      · jax.compiles (executables compiled or loaded, under recording())
        └─▶ counts (repro.obs.spans.count: SpanRecorder.counts plus a
            COUNT_PREFIX annotation on the timeline, no-op without a
            recorder; byte sums only while is_recording())
      counters / gauges / histograms / LatencyRecorder
        └─▶ Registry (repro.obs.metrics: one named home per run;
            LatencyRecorder/LatencyReport live here — repro.serve
            re-exports them)

    STATIC (tracing only, nothing executes)
      dispatch_count(fn, *args) (repro.obs.dispatch)
        └─▶ (#pallas_call, exact?) — the J002 counter, promoted to a
            reusable hook; repro.analysis.jaxpr_lint re-imports it.

    EXPORT (repro.obs.export)
      Registry ──▶ JSONL (spans + metrics + trace/latency events +
                   provenance block) ──▶ `python -m repro.obs` report
                   (convergence table, comm frontier, span waterfall,
                   serve percentiles)
      provenance() / stamp_provenance() — git sha, jax version, device
      kind, interpret flag stamped into every BENCH_*.json by
      benchmarks/run.py.

On-device vs host is a hard line: device traces are arrays computed by
the solver program itself (exact, replayable, backend-agnostic); host
spans/metrics are wall-clock observations (machine-dependent, for
waterfalls and percentiles). The exporters carry both, tagged by kind.

Importing `repro.obs` (and `.metrics`/`.trace`/`.spans`/`.export`) does
NOT import jax — the analysis CLI configures the jax platform first and
times itself with obs clocks. Only `dispatch_count` and an installed
recorder touch jax, lazily.
"""
from repro.obs import export, spans
from repro.obs.dispatch import count_pallas_dispatches, dispatch_count
from repro.obs.metrics import (Counter, FakeClock, Gauge, Histogram,
                               LatencyRecorder, LatencyReport, Registry,
                               perf_clock, wall_clock)
from repro.obs.spans import Span, SpanRecorder, count, recording, span
from repro.obs.trace import AsyncSolveTrace, SolveTrace

__all__ = [
    "AsyncSolveTrace",
    "Counter",
    "FakeClock",
    "Gauge",
    "Histogram",
    "LatencyRecorder",
    "LatencyReport",
    "Registry",
    "SolveTrace",
    "Span",
    "SpanRecorder",
    "count",
    "count_pallas_dispatches",
    "dispatch_count",
    "export",
    "perf_clock",
    "recording",
    "span",
    "spans",
    "wall_clock",
]
