"""Process-wide cumulative performance counters, and spans in the JAX
profiler's trace.

A deliberately tiny facility: components bump named counters in bulk at
natural boundaries (an engine run's end, a memo lookup, a serving step),
never per-event in a hot loop, so the counters are always on and cost
nothing measurable. ``benchmarks/run.py --profile`` snapshots the table
before/after each section and writes the per-phase deltas into the JSON
record (schema ``bench_dcache/v3``), which is what lets a perf regression be
localised to a phase *and* a mechanism (e.g. "the admission table's wall
grew because sketch flushes tripled") without rerunning under a profiler.

``span`` marks a region of host code in the JAX profiler's own trace, on the
same clock as the device's events, so an idle gap on the chip can be put
down to what the host was doing. The profiler being on or off is its only
switch: while no trace is active a span records nothing.
"""
from __future__ import annotations

import contextlib
import threading
from collections import defaultdict
from typing import ContextManager, Dict, Mapping

COUNTERS: Dict[str, float] = defaultdict(float)
_LOCK = threading.Lock()     # --parallel runs cells on a thread pool
_NO_SPAN = contextlib.nullcontext()
_compile_listener = False

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def add(name: str, value: float = 1.0) -> None:
    """Accumulate ``value`` into the named counter (thread-safe: the
    read-modify-write must not lose increments under ``--parallel``)."""
    with _LOCK:
        COUNTERS[name] += value


def add_many(deltas: Mapping[str, float]) -> None:
    """Accumulate several counters under one lock (one call per serving
    step, say, rather than one per event)."""
    with _LOCK:
        for name, value in deltas.items():
            COUNTERS[name] += value


def snapshot() -> Dict[str, float]:
    """Point-in-time copy of every counter."""
    with _LOCK:
        return dict(COUNTERS)


def delta(before: Dict[str, float],
          after: Dict[str, float]) -> Dict[str, float]:
    """Counter increments between two snapshots (zero-delta keys omitted;
    values rounded for stable JSON)."""
    out = {}
    for k, v in after.items():
        d = v - before.get(k, 0.0)
        if d:
            out[k] = round(d, 6)
    return out


def span(name: str, **meta) -> ContextManager:
    """A ``jax.profiler.TraceAnnotation`` named ``name`` with ``meta`` as
    its stats, while a trace is active; otherwise a shared no-op context."""
    from jax.profiler import TraceAnnotation
    if not TraceAnnotation.is_enabled():
        return _NO_SPAN
    return TraceAnnotation(name, **meta)


def count_compiles() -> None:
    """Count the process's backend compiles into ``jax.compiles`` and their
    seconds into ``jax.compile_s``. One listener per process, however often
    this is called. A load from the persistent compile cache is not a
    backend compile and is not counted."""
    global _compile_listener
    with _LOCK:
        if _compile_listener:
            return
        _compile_listener = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_compile)


def _on_compile(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        add_many({"jax.compiles": 1, "jax.compile_s": duration})
