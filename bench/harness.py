"""One run of one cell: set-up, window, judgement, result line.

`run()` is what `bench/run.py` calls. `run_cell()` is the same run
without the look for a chip, so that tests can drive it on the CPU.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import shutil
import sys
import time

import jax

from bench import check, registry, roofline, trace as btrace


class NoChip(RuntimeError):
    pass


def require_chips(chips: int) -> list:
    """The TPU devices, or NoChip when there are none or too few."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, found "
                     f"{len(devices)}")
    return devices


class Phases:
    """The benchmark's host spans around each layer call.

    Always recorded on the host clock; in a traced run also written into
    the profiler's trace as `jax.profiler.TraceAnnotation("bench.<name>")`,
    and `sync` then ends each phase on the device so that its device work
    falls inside its span. In an untraced run `sync` does nothing: the
    path runs as a user would run it."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.spans: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.traced:
            with jax.profiler.TraceAnnotation("bench." + name):
                yield
        else:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def sync(self, tree) -> None:
        if self.traced:
            jax.block_until_ready(tree)


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader sees of a traced run."""

    cell: registry.Cell
    result: dict                  # the generator's window result
    trace: btrace.Trace
    devices: list[str]            # trace planes of the chips the cell used
    program_spans: list           # repro.obs spans recorded in the window
    peak: dict                    # roofline.peaks of the device
    state: dict                   # the generator's state


def _quiet_heap() -> None:
    """Collect, then freeze what set-up allocated, so that the window's
    garbage collections do not walk the set-up's objects (the queries
    and data the benchmark made)."""
    gc.collect()
    gc.freeze()


def _device_planes(tr: btrace.Trace, chips: int) -> list[str]:
    planes = sorted(tr.ops, key=lambda n: int(n.rsplit(":", 1)[1])
                    if n.rsplit(":", 1)[1].isdigit() else 0)
    return planes[:chips]


def enable_cache() -> None:
    """The program's persistent compilation cache, with every program in
    it: JAX leaves out programs that compile in under a second, and a run
    that compiled those again would pay it in set-up."""
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(cell: registry.Cell, seed: int, seconds: float, traced: bool,
             *, t_process: float, trace_dir: str, device_count: int):
    """Set up, measure, judge. Returns (result dict, check table)."""
    gen = cell.generator
    phases = Phases(False)
    state = gen.setup(cell, seed, phases)
    setup_s = time.perf_counter() - t_process
    for name, t0, t1 in phases.spans:
        print(f"setup {name}: {t1 - t0:.3f} s", file=sys.stderr)

    phases = Phases(traced)
    program_spans = []
    if traced:
        from repro.obs.spans import recording

        shutil.rmtree(trace_dir, ignore_errors=True)
        _quiet_heap()
        with recording() as rec, jax.profiler.trace(
                trace_dir, profiler_options=btrace.profiler_options()):
            with jax.profiler.TraceAnnotation("bench.window"):
                result = gen.window(state, seconds, phases)
        program_spans = list(rec.spans)
    else:
        _quiet_heap()
        result = gen.window(state, seconds, phases)
    gc.unfreeze()

    devices = jax.devices()[:cell.chips]
    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": device_count,
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}

    metrics, breakdown = {}, None
    if traced:
        tr = btrace.load(trace_dir)
        planes = _device_planes(tr, cell.chips)
        lo, hi = tr.window()
        device["busy_s"] = sum(btrace.busy_ns(tr, p, lo, hi)
                               for p in planes) / len(planes) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        view = RunView(cell=cell, result=result, trace=tr, devices=planes,
                       program_spans=program_spans,
                       peak=roofline.peaks(device["kind"], cell.root),
                       state=state)
        for m in cell.per_layer:
            value = registry.load_metric(cell.root, m["name"]).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": btrace.top_ops(tr),
                     "idle_gaps": btrace.idle_gaps(tr)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = dict(result["end_to_end"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    # The reference runs after the window, with the program's state
    # dropped, on the host CPU.
    sample = gen.check_outputs(state)
    gc.collect()
    readings = gen.judge(state, sample)
    numbers = check.worst(readings) if readings else {}
    ok, table = check.verdict(numbers, cell.config["limits"])
    correct = (ok and bool(table) and result["failed"] == 0
               and result["attempted"] > 0)
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = table
    return out, table


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        root: str, t_process: float, trace_dir: str) -> int:
    cell = registry.load_cell(workload, root)
    try:
        devices = require_chips(cell.chips)
    except NoChip as exc:
        print(f"bench: {exc}; nothing was run", file=sys.stderr)
        return 2
    enable_cache()
    out, table = run_cell(cell, seed, seconds, traced, t_process=t_process,
                          trace_dir=trace_dir, device_count=len(devices))
    sys.stdout.flush()
    check.print_table(table)
    sys.stderr.flush()
    print(json.dumps(_finite(out), allow_nan=False))
    sys.stdout.flush()
    return 0


def _finite(tree):
    """The result with each non-finite number written as a string, so
    that the line is strict JSON."""
    if isinstance(tree, dict):
        return {k: _finite(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_finite(v) for v in tree]
    if isinstance(tree, float) and not math.isfinite(tree):
        return str(tree)
    return tree
