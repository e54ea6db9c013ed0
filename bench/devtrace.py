"""Device trace: capture with the JAX profiler, extract, reduce.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
what the metrics need: the executions of each compiled module and each
device operation on the first TPU, and the benchmark's own host spans.
``reduce`` turns that into busy time, idle gaps tagged by the host span
open during them, and device time per module. Both work on plain lists, so
a small recorded extract can be checked without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

SPANS = ("engine.step", "bench.generate", "bench.wait_arrival")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def extract(trace_dir: str) -> Dict:
    """Device events of ``/device:TPU:0`` and the host spans of a trace."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device, host, planes = [], [], {}
    tpus = sorted(p.name for p in pd.planes
                  if re.fullmatch(r"/device:TPU:\d+", p.name))
    for plane in pd.planes:
        planes[plane.name] = [ln.name for ln in plane.lines]
        if tpus and plane.name == tpus[0]:
            for ln in plane.lines:
                if ln.name not in (MODULES_LINE, OPS_LINE):
                    continue
                for e in ln.events:
                    device.append({"line": ln.name, "name": e.name,
                                   "start_ns": float(e.start_ns),
                                   "dur_ns": float(e.duration_ns)})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in SPANS:
                        host.append({"name": e.name,
                                     "start_ns": float(e.start_ns),
                                     "dur_ns": float(e.duration_ns)})
    return {"planes": planes, "device": device, "host": host}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(base: Sequence[Interval], cut: Sequence[Interval]):
    """``base`` minus ``cut``; both are unions (sorted, disjoint)."""
    out = []
    for a, b in base:
        cur = a
        for c, d in cut:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
        if cur < b:
            out.append((cur, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


CONTAINERS = ("%while", "%conditional", "%call")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    work_s: float           # window minus the waits for an arrival
    idle_with_work_s: float
    module_s: Dict[str, float]   # device seconds per compiled module
    module_n: Dict[str, int]     # executions per compiled module
    op_s: Dict[Tuple[str, str], float]   # (module, op) -> device seconds
    idle_gaps: List[List]

    def modules(self, name: str, executions: int) -> List[str]:
        """The modules that run the step ``name`` (``"decode_step"``):
        every ``jit_<name>`` where the program names it, one for each shape
        the step compiled at, else the module with the most device time
        among those that ran exactly ``executions`` times (the program's
        jitted partials all trace as ``jit__unknown``)."""
        named = [k for k in self.module_s if program(k) == f"jit_{name}"]
        if named:
            return named
        same = [k for k, n in self.module_n.items() if n == executions]
        return [max(same, key=self.module_s.get)] if same else []

    def top_ops(self, labels: Dict[str, str], top: int = 10) -> List[List]:
        """Leaf device operations by total time, as ``label:op``; an op of
        every module that shares a label is one entry with their summed
        time."""
        op_s: Dict[str, float] = {}
        for (m, o), v in self.op_s.items():
            k = f"{labels.get(m, program(m))}:{o}"
            op_s[k] = op_s.get(k, 0.0) + v
        return [[k, v] for k, v in
                sorted(op_s.items(), key=lambda kv: -kv[1])[:top]]


def program(module: str) -> str:
    """A module's name without the fingerprint of the compiled program:
    ``jit_prefill_step(1234)`` -> ``jit_prefill_step``."""
    return re.sub(r"\(\d+\)$", "", module)


def reduce(ev: Dict, top: int = 10) -> Optional[Summary]:
    spans = [(h["name"], h["start_ns"], h["start_ns"] + h["dur_ns"])
             for h in ev["host"]]
    dev = ev["device"]
    if not spans or not dev:
        return None
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    ops = sorted((d["start_ns"], d["start_ns"] + d["dur_ns"],
                  d["name"].split(" = ")[0]) for d in dev
                 if d["line"] == OPS_LINE)
    mods = sorted((d["start_ns"], d["start_ns"] + d["dur_ns"], d["name"])
                  for d in dev if d["line"] == MODULES_LINE)
    busy = union(clip([(a, b) for a, b, _ in (ops or mods)], lo, hi))
    window = [(lo, hi)]
    waits = union([(s, e) for n, s, e in spans if n == "bench.wait_arrival"])
    work = subtract(window, waits)
    busy_in_work = subtract(busy, subtract(busy, work))
    module_s: Dict[str, float] = {}
    module_n: Dict[str, int] = {}
    for a, b, k in mods:
        module_s[k] = module_s.get(k, 0.0) + (b - a) * 1e-9
        module_n[k] = module_n.get(k, 0) + 1
    op_s: Dict[Tuple[str, str], float] = {}
    j = 0
    for a, b, name in ops:
        if name.startswith(CONTAINERS):
            continue
        while j + 1 < len(mods) and mods[j + 1][0] <= a:
            j += 1
        mod = mods[j][2] if mods and mods[j][0] <= a < mods[j][1] else "?"
        op_s[(mod, name)] = op_s.get((mod, name), 0.0) + (b - a) * 1e-9
    gaps = subtract(window, busy)

    def tag(a, b):
        mid = 0.5 * (a + b)
        open_ = [n for n, s, e in spans if s <= mid < e]
        return open_[-1] if open_ else "untraced"

    idle = sorted(((tag(a, b), (b - a) * 1e-9) for a, b in gaps),
                  key=lambda t: -t[1])[:top]
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=length(busy) * 1e-9,
        work_s=length(work) * 1e-9,
        idle_with_work_s=(length(work) - length(busy_in_work)) * 1e-9,
        module_s=module_s, module_n=module_n, op_s=op_s,
        idle_gaps=[[k, v] for k, v in idle])
