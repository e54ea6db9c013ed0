#!/usr/bin/env python3
"""One traced run of a cell, read through the program's own spans and
counters.

    python bench/tools/program_trace.py --workload granite-3-2b.decisions \
        --seed <n> --seconds 50 [--fixture PATH]

Runs the cell as ``bench/run.py ... --trace 1`` does and prints that run's
result line. Then it prints a second line, read from what the serving engine
records itself: the ``serving.*`` spans that ``ServingEngine.step`` writes
into the profiler's trace, and the ``serving.*`` counters from the window's
open to its close (before the drain). That line holds

- ``host_reads_per_step``: blocking device-to-host reads per engine step;
- ``decode_host_gap_ms``: median, over the traced ``serving.step`` spans that
  hold no ``serving.admit``, of the span's length less the device's busy
  time inside it;
- ``admit_ms_per_request``: mean length of the traced ``serving.admit``
  spans;
- ``prefill_pad_pct``: share of the prefilled bucket tokens that are
  padding;
- the traced window's idle gaps, each tagged with the innermost span open
  during it (the open span that started last; garbage collections are
  spans too while the tool traces), their offsets in the window, idle
  seconds summed by that tag, and the host events open in the three
  longest;
- host seconds and counts per span name;
- the end-to-end metrics of this traced run, and the wall of the steps
  that admit nothing, traced and not, by number of decoding slots: what
  tracing costs.

``--fixture`` writes a few traced steps around one admission, with their
device events, spans and the run's counters, as gzipped JSON: the recorded
trace that ``bench/tests/test_bench_spans.py`` reads.
"""
from __future__ import annotations

import gc
import glob
import gzip
import json
import os
import pathlib
import shutil
import statistics
import sys
import time
from typing import Dict, List, Optional, Sequence

T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import devtrace, harness  # noqa: E402
from repro.core import profiling  # noqa: E402

PROGRAM = ("serving.", "gc.")
FIXTURE_STEPS = 7


def _profile(trace_dir: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1])


def program_spans(trace_dir: str) -> List[Dict]:
    """The program's ``serving.*`` host spans of a trace, with their
    metadata (``rid``, ``slot``, ``prompt_len``, ``bucket``), and the
    ``gc.*`` spans that ``KeepingTracer`` adds."""
    out = []
    for plane in _profile(trace_dir).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(PROGRAM):
                    out.append({"name": e.name,
                                "start_ns": float(e.start_ns),
                                "dur_ns": float(e.duration_ns),
                                "meta": {k: v for k, v in e.stats}})
    return out


def _intervals(spans: Sequence[Dict], name: Optional[str] = None):
    return [(s["start_ns"], s["start_ns"] + s["dur_ns"]) for s in spans
            if name is None or s["name"] == name]


def busy(ev: Dict) -> List[devtrace.Interval]:
    """The device's busy intervals, as ``devtrace.reduce`` takes them."""
    ops = [d for d in ev["device"] if d["line"] == devtrace.OPS_LINE]
    return devtrace.union(_intervals(
        ops or [d for d in ev["device"]
                if d["line"] == devtrace.MODULES_LINE]))


def innermost(spans: Sequence[Dict], t: float) -> str:
    """The span open at ``t`` that started last (the shorter of two that
    started together), or ``untraced``."""
    open_ = [(s["start_ns"], -s["dur_ns"], s["name"]) for s in spans
             if s["start_ns"] <= t < s["start_ns"] + s["dur_ns"]]
    return max(open_)[2] if open_ else "untraced"


def idle(ev: Dict, top: int = 10) -> Dict:
    """The traced window's idle gaps tagged by the innermost open span, the
    longest first, with their start from the window's start; and idle
    seconds by tag."""
    spans = ev["host"] + ev["program"]
    lo = min(s for s, _ in _intervals(ev["host"]))
    hi = max(e for _, e in _intervals(ev["host"]))
    gaps = devtrace.subtract([(lo, hi)], devtrace.clip(busy(ev), lo, hi))
    tagged = sorted(((innermost(spans, 0.5 * (a + b)), (b - a) * 1e-9,
                      (a - lo) * 1e-9) for a, b in gaps),
                    key=lambda g: -g[1])
    by_tag: Dict[str, float] = {}
    for tag, d, _ in tagged:
        by_tag[tag] = by_tag.get(tag, 0.0) + d
    dev = [d["start_ns"] for d in ev["device"]]
    return {"window_s": (hi - lo) * 1e-9,
            "first_device_event_s": (min(dev) - lo) * 1e-9 if dev else None,
            "idle_gaps": [list(g) for g in tagged[:top]],
            "idle_s_by_span": dict(sorted(by_tag.items(),
                                          key=lambda kv: -kv[1]))}


def readings(ev: Dict, counters: Dict[str, float]) -> Dict[str, float]:
    """The four per-layer numbers of the engine's spans and counters; a
    number the trace or counters cannot give is left out."""
    out = {}
    c = counters
    if c.get("serving.steps"):
        out["host_reads_per_step"] = c["serving.host_reads"] \
            / c["serving.steps"]
    if c.get("serving.prefill_padded_tokens"):
        out["prefill_pad_pct"] = 100.0 * (
            c["serving.prefill_padded_tokens"] - c["serving.prefill_tokens"]) \
            / c["serving.prefill_padded_tokens"]
    if not ev["device"]:        # no chip in the trace: no span numbers
        return out
    prog = ev["program"]
    admits = _intervals(prog, "serving.admit")
    if admits:
        out["admit_ms_per_request"] = 1e-6 * statistics.fmean(
            b - a for a, b in admits)
    dev = busy(ev)
    gaps = [(b - a) - devtrace.length(devtrace.clip(dev, a, b))
            for a, b in _intervals(prog, "serving.step")
            if not any(a <= s and e <= b for s, e in admits)]
    if gaps:
        out["decode_host_gap_ms"] = 1e-6 * statistics.median(gaps)
    return out


def host_time(ev: Dict) -> Dict[str, List[float]]:
    """Per span name: how many, and their host seconds in all."""
    out: Dict[str, List[float]] = {}
    for s in ev["host"] + ev["program"]:
        n_s = out.setdefault(s["name"], [0, 0.0])
        n_s[0] += 1
        n_s[1] += s["dur_ns"] * 1e-9
    return out


def step_walls(run: harness.Run) -> Dict[int, List]:
    """Per number of decoding slots, the median wall (ms) of the window's
    steps that admit nothing, traced and not, and how many of each: what
    the profiler costs the loop while it records, at equal occupancy."""
    walls: Dict[int, List[List[float]]] = {}
    for s in run.window_steps():
        if s.keys and not s.admitted:
            walls.setdefault(len(s.keys), [[], []])[0 if s.traced else 1] \
                .append(1e3 * (s.t1 - s.t0))
    return {k: [statistics.median(t) if t else None,
                statistics.median(u) if u else None, len(t), len(u)]
            for k, (t, u) in sorted(walls.items())}


def host_stacks(trace_dir: str, times: Sequence[float],
                depth: int = 8) -> List[List[str]]:
    """For each time, the host events open then on the thread that holds
    the most of them (the profiler's Python tracer records every call),
    outermost first: the innermost ``depth``, and the engine's innermost
    span where it lies further out (``stack_tail``)."""
    found: List[Dict] = [{} for _ in times]
    for plane in _profile(trace_dir).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                a = e.start_ns
                b = a + e.duration_ns
                for i, t in enumerate(times):
                    if a <= t < b:
                        found[i].setdefault(ln.name, []).append(
                            (a, -b, e.name))
    return [stack_tail([n for _, _, n in sorted(max(f.values(), key=len))],
                       depth) if f else [] for f in found]


def stack_tail(frames: List[str], depth: int) -> List[str]:
    """The innermost ``depth`` of ``frames`` (outermost first), after the
    innermost ``serving.*`` span where none is among them: a gap's midpoint
    often lands deep inside JAX's dispatch, below the engine's span."""
    kept = frames[-depth:]
    if not any(n.startswith("serving.") for n in kept):
        kept = [n for n in frames[:-depth]
                if n.startswith("serving.")][-1:] + kept
    return kept


def fixture(ev: Dict, steps: List[Dict], counters: Dict[str, float],
            n: int = FIXTURE_STEPS) -> Optional[Dict]:
    """``n`` traced engine steps around the first that admits, with the
    device events and spans inside them; ``steps`` are the loop's records
    of the traced steps, one per ``engine.step`` span."""
    eng = sorted(_intervals(ev["host"], "engine.step"))
    admits = _intervals(ev["program"], "serving.admit")
    first = next((i for i, (a, b) in enumerate(eng)
                  if any(a <= s < b for s, _ in admits)), None)
    if first is None or len(steps) != len(eng):
        return None
    i0 = max(0, min(first - n // 2, len(eng) - n))
    lo, hi = eng[i0][0], eng[min(i0 + n, len(eng)) - 1][1]

    def inside(x):
        return lo <= x["start_ns"] and x["start_ns"] + x["dur_ns"] <= hi

    return {"device": [d for d in ev["device"]
                       if lo <= d["start_ns"] < hi],
            "host": [h for h in ev["host"] if inside(h)],
            "program": [p for p in ev["program"] if inside(p)],
            "steps": steps[i0:i0 + n], "counters": counters}


class KeepingTracer(harness._Tracer):
    """The benchmark's tracer, which also keeps the program's spans, the
    counters at the window's open and close (its stop), what the host was
    doing in the longest idle gaps, and marks each garbage collection while
    it records as a ``gc.gen<n>`` span."""
    last: Optional["KeepingTracer"] = None

    def __init__(self):
        super().__init__()
        self.at_open: Dict[str, float] = {}
        self.at_close: Dict[str, float] = {}
        self.events: Optional[Dict] = None
        self.stacks: List[List[str]] = []
        self._gc_span = None
        KeepingTracer.last = self

    def _on_gc(self, phase: str, info: Dict) -> None:
        import jax
        if phase == "start":
            self._gc_span = jax.profiler.TraceAnnotation(
                f"gc.gen{info['generation']}")
            self._gc_span.__enter__()
        elif self._gc_span is not None:
            self._gc_span.__exit__(None, None, None)
            self._gc_span = None

    def open(self):
        self.at_open = profiling.snapshot()

    def start(self):
        super().start()
        gc.callbacks.append(self._on_gc)

    def stop(self):
        self.at_close = profiling.snapshot()
        gc.callbacks.remove(self._on_gc)
        super().stop()

    def summary(self):
        try:
            self.events = dict(devtrace.extract(self.dir),
                               program=program_spans(self.dir))
            gaps = idle(self.events, top=3)["idle_gaps"]
            lo = min(s for s, _ in _intervals(self.events["host"]))
            self.stacks = host_stacks(self.dir, [
                lo + 1e9 * (start + 0.5 * d) for _, d, start in gaps])
            return devtrace.reduce(self.events)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", help="where to write the recorded steps")
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    try:
        device = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"program_trace: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # run_cell builds its tracer by this name
    harness._Tracer = KeepingTracer
    run, checks, _ = harness.run_cell(
        cell, args.seed, args.seconds, True, t_start=T0,
        peaks=harness.peaks_for(device["kind"]))
    tracer = KeepingTracer.last
    print(json.dumps(harness.result_line(cell, run, checks, device, True)))
    counters = profiling.delta(tracer.at_open, tracer.at_close)
    ev = tracer.events
    print(json.dumps({"program": readings(ev, counters), **idle(ev),
                      "host_s_by_span": host_time(ev),
                      "window_counters": counters,
                      "end_to_end": harness.read_metrics(run,
                                                         cell.end_to_end),
                      "step_wall_ms_by_slots": step_walls(run),
                      "host_stacks_of_longest_gaps": tracer.stacks}),
          flush=True)
    if args.fixture:
        steps = [{"admitted": s.admitted, "keys": s.keys}
                 for s in run.traced_steps()]
        rec = fixture(ev, steps, counters)
        if rec is None:
            print("program_trace: no fixture: the traced steps hold no "
                  "admission or do not match the trace", file=sys.stderr)
            return 1
        with gzip.open(args.fixture, "wt") as f:
            json.dump(rec, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
