"""One run of one benchmark cell on the chip.

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json``; each metric is read by ``bench/metrics/<name>.py``. The
configuration file names the two modules that know its model: its plain
float32 reference (``"reference"``) and the work counts of its steps
(``"work"``). A run builds the program's ``ServingEngine`` (weights drawn on
the device from the seed), warms up the shapes the cell uses, drives the
mix's lead-in and then the window through ``ServingEngine.submit`` and
``ServingEngine.step``, follows every request due in the window to its end
while the schedule goes on, and then holds a sample of the served tokens to
the configuration's reference.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
import types
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import devtrace, traffic
from bench.reference_common import gaps, served_rows

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
BENCHMARK_JSON = ROOT / "BENCHMARK.json"
PEAKS_JSON = BENCH / "peaks.json"
TRACE_SECONDS = 5.0         # traced tail of the window in a --trace 1 run
DRAIN_SECONDS = 120.0       # how long requests due in the window may take
WARMUP_NEW_TOKENS = 3
SAMPLE_TOKENS = 400         # served tokens the reference checks per run
SAMPLE_MAX_REQUESTS = 4
NO_ANSWER = 1e9             # the gap reported where no served token exists


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- finding things by name ----------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict            # the configuration file's contents
    reference: types.ModuleType     # the modules the configuration names
    work: types.ModuleType
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    limits: Dict


def _for_cell(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def find_cell(name: str, bench_json: pathlib.Path = BENCHMARK_JSON) -> Cell:
    """The cell ``name``. Its configuration file, the modules that file
    names and its limits are found beside ``bench_json``; its mix and the
    metric readers in this directory."""
    root = bench_json.parent
    bench = json.loads(bench_json.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_json.name}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    named = {}
    for key in ("reference", "work"):
        if key not in config:
            raise ValueError(f"{conf['file']} names no {key!r} module")
        named[key] = load_module(root / config[key],
                                 f"bench_{key}_{w['config']}")
    return Cell(name=name, chips=w["chips"], config=config, **named,
                mix=traffic.load_mix(w["traffic"]),
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name),
                limits=json.loads((root / "bench" / "limits"
                                   / f"{name}.json").read_text()))


def load_module(path: pathlib.Path, name: str) -> types.ModuleType:
    """The Python file at ``path``, loaded as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable:
    return load_module(BENCH / "metrics" / f"{name}.py",
                       f"bench_metric_{name}").read


def peaks_for(kind: str) -> Dict:
    table = json.loads(PEAKS_JSON.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS_JSON.name}")
    return table[kind]


# -- the device ------------------------------------------------------------------

def require_chips(n: int) -> Dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileClock:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def program_config(config: Dict, reference: types.ModuleType):
    """The program's ``ModelConfig`` for a configuration file; it has to be
    the model the file states, as the file's ``reference`` reads it."""
    from repro.configs import get_config
    cfg = get_config(config["arch"])
    got = reference.model_block(cfg)
    if got != config["model"]:
        diff = {k: (got.get(k), v) for k, v in config["model"].items()
                if got.get(k) != v}
        raise ValueError(f"the program's {config['arch']} differs from the "
                         f"configuration file: {diff}")
    return cfg


def engine_seed(seed: int) -> int:
    return seed % 2 ** 32


# -- the run record ----------------------------------------------------------------

@dataclasses.dataclass
class Req:
    spec: traffic.RequestSpec
    due: float
    submitted: float
    handle: object                  # the engine's Request
    admit_t: Optional[float] = None  # start of the step() that admitted it
    token_t: List[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return bool(self.handle.done)


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    admitted: List[int]     # true prompt lengths prefilled in this step
    keys: List[int]         # per decoding slot, the keys it attends to
    traced: bool
    in_window: bool


@dataclasses.dataclass
class Run:
    cell: str
    model: Dict
    work: types.ModuleType  # the configuration's work counts
    seed: int
    seconds: float
    setup_s: float
    t_open: float
    t_close: float
    requests: List[Req]     # attempted: due in the window, or admitted by it
    withdrawn: int
    steps: List[Step]
    compiles_in_window: int
    compile_s_in_window: float
    memory_peak_bytes: int
    peaks: Dict
    trace: Optional[devtrace.Summary] = None
    trace_overhead_s: float = 0.0
    # host intervals in which the benchmark itself held the loop (the
    # profiler's start and its stop at the close of a traced window)
    paused: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)

    # what the metric readers share
    def in_window(self, t: float) -> bool:
        return self.t_open <= t <= self.t_close

    def unpaused(self, a: float, b: float) -> float:
        """Seconds from ``a`` to ``b``, less the benchmark's own pauses."""
        return (b - a) - sum(max(0.0, min(b, q) - max(a, p))
                             for p, q in self.paused)

    def window_tokens(self) -> int:
        return sum(1 for r in self.requests for t in r.token_t
                   if self.in_window(t))

    def itl_gaps(self) -> List[float]:
        return [b - a for r in self.requests
                for a, b in zip(r.token_t, r.token_t[1:])
                if self.in_window(b)]

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if s.in_window]

    def traced_steps(self) -> List[Step]:
        return [s for s in self.steps if s.traced]

    def traced_decodes(self) -> List[Step]:
        return [s for s in self.traced_steps() if s.keys]

    def traced_prefills(self) -> List[int]:
        """True prompt lengths of the prefills in the traced window."""
        return [n for s in self.traced_steps() for n in s.admitted]

    def traced_count(self, step: str) -> int:
        """Executions of ``step`` the loop saw in the traced window."""
        return len({"decode_step": self.traced_decodes,
                    "prefill_step": self.traced_prefills}[step]())

    def module_roles(self) -> Dict[str, str]:
        """Which traced modules ran decode_step and which prefill_step: by
        name, every program a step compiled; else told apart by how often
        each ran against what the loop saw."""
        if self.trace is None:
            return {}
        roles = {}
        for step in ("decode_step", "prefill_step"):
            for k in self.trace.modules(step, self.traced_count(step)):
                roles.setdefault(k, step)
        return roles

    def device_seconds(self, step: str) -> Optional[float]:
        """Device time of the traced executions of ``step``, summed over
        every module that ran it, or None where those modules' executions
        are not the loop's one for one."""
        keys = [k for k, v in self.module_roles().items() if v == step]
        if not keys or sum(self.trace.module_n[k] for k in keys) \
                != self.traced_count(step):
            return None
        return sum(self.trace.module_s[k] for k in keys)


@contextlib.contextmanager
def span(name: str):
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class _Tracer:
    """The JAX profiler over the end of the window, writing under $TMPDIR."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.overhead_s = 0.0
        self.paused: List[Tuple[float, float]] = []

    def open(self):
        """The window opens; the trace starts later."""

    def start(self):
        """Starts the trace inside the window; the seconds it takes (up to
        a few on the chip) are a pause of the loop."""
        import jax
        self._timed(jax.profiler.start_trace, self.dir)

    def stop(self):
        """Stops the trace at the close; the seconds it takes (the trace is
        collected and written) are a pause of the loop."""
        import jax
        self._timed(jax.profiler.stop_trace)

    def _timed(self, fn, *args):
        t = time.perf_counter()
        fn(*args)
        t1 = time.perf_counter()
        self.overhead_s += t1 - t
        self.paused.append((t, t1))

    def summary(self) -> Optional[devtrace.Summary]:
        try:
            return devtrace.reduce(devtrace.extract(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- set-up, window, drain ------------------------------------------------------------

def warm_up(eng, rows: List[Dict]) -> None:
    """Every program and op the window runs: a prefill into each slot at the
    prompts' bucket, the install scatters, decode steps and the sampling."""
    import jax
    for i in range(eng.max_batch):
        eng.submit(rows[i % len(rows)]["prompt"],
                   max_new_tokens=WARMUP_NEW_TOKENS)
    eng.run_until_done()
    jax.block_until_ready(eng.cache)


def drive(eng, tr: traffic.Traffic, seconds: float,
          tracer: Optional[_Tracer] = None,
          trace_seconds: float = TRACE_SECONDS):
    """The lead-in, the measured window and the drain after it. Requests
    due before the open or after the close load the engine and are not
    returned; once every request due in the window has ended (or the drain
    has run out), those still in the engine are dropped from it."""
    pc = time.perf_counter
    reqs: List[Req] = []
    live: List[Req] = []
    steps: List[Step] = []
    pending = collections.deque(tr.lead_in + tr.specs)
    after = tr.after()
    backlog = None if tr.open_loop else tr.backlog()
    t_open = pc() + tr.lead_in_s
    t_close = t_open + seconds
    trace_at = max(t_open, t_close - trace_seconds) if tracer else math.inf
    tracing = False
    opened = tracer is None

    def submit(spec, due):
        h = eng.submit(spec.prompt, max_new_tokens=spec.max_new_tokens)
        r = Req(spec, due, pc(), h)
        if spec.due_s is None or 0 <= spec.due_s < seconds:
            reqs.append(r)
        live.append(r)

    def step():
        t0 = pc()
        with span("engine.step"):
            eng.step()
        t1 = pc()
        admitted, keys = [], []
        for r in live:
            h = r.handle
            if r.admit_t is None and h.first_token_at is not None:
                r.admit_t = t0
                admitted.append(len(h.prompt_ids))
            n = len(h.out_ids)
            grew = n > len(r.token_t)
            while len(r.token_t) < n:
                r.token_t.append(h.first_token_at if not r.token_t else t1)
            if grew and n >= 2:
                keys.append(len(h.prompt_ids) + n - 1)
        live[:] = [r for r in live if not r.done]
        steps.append(Step(t0, t1, admitted, keys, tracing,
                          t_open <= t0 < t_close))

    while True:
        now = pc()
        if now >= t_close:
            break
        if not opened and now >= t_open:
            tracer.open()
            opened = True
        if not tracing and now >= trace_at:
            tracer.start()
            tracing = True
        with span("bench.generate"):
            if backlog is None:
                while pending and t_open + pending[0].due_s <= now:
                    spec = pending.popleft()
                    submit(spec, t_open + spec.due_s)
            else:
                while len(eng.waiting) < eng.max_batch:
                    submit(next(backlog), pc())
        if live:
            step()
        else:
            nxt = t_open + pending[0].due_s if pending else t_close
            with span("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt, t_close) - pc()))
    if tracing:
        tracer.stop()
        tracing = False
    while pending and t_open + pending[0].due_s <= t_close:
        spec = pending.popleft()        # due before the close, not yet sent
        submit(spec, t_open + spec.due_s)
    withdrawn = 0
    if backlog is not None:
        # backlog requests still queued at the close were never attempted
        queued = {id(h) for h in eng.waiting}
        eng.waiting.clear()
        withdrawn = sum(1 for r in reqs if id(r.handle) in queued)
        reqs = [r for r in reqs if id(r.handle) not in queued]
        live[:] = [r for r in live if id(r.handle) not in queued]
    nxt = next(after, None)
    deadline = pc() + DRAIN_SECONDS
    while any(not r.done for r in reqs) and pc() < deadline:
        while nxt is not None and t_open + nxt.due_s <= pc():
            submit(nxt, t_open + nxt.due_s)     # the schedule goes on
            nxt = next(after, None)
        step()
    eng.waiting.clear()                 # none of these is measured
    eng.slots[:] = [None] * len(eng.slots)
    return reqs, withdrawn, steps, t_open, t_close


# -- correctness --------------------------------------------------------------------

def pick_sample(reqs: List[Req], seed: int) -> List[Req]:
    """The longest finished request and others drawn from the seed, until
    ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_MAX_REQUESTS`` requests."""
    done = [r for r in reqs if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.handle.out_ids), -r.spec.idx))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out, toks = [longest], len(longest.handle.out_ids)
    for i in order:
        if toks >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(rest[i])
        toks += len(rest[i].handle.out_ids)
    return out


def sample_rows(sample: List[Req]):
    seqs = [(list(r.handle.prompt_ids), list(r.handle.out_ids))
            for r in sample]
    length = -(-max(len(p) + len(s) for p, s in seqs) // 256) * 256
    return served_rows(seqs, length)


def logit_gaps(reference: types.ModuleType, model: Dict, seed: int, rows,
               quant: Optional[str] = None):
    """Per served token, its gap below ``reference``'s best logit; with
    ``quant`` also the gaps of the tokens the quantised control puts first."""
    tokens, rb, rp, tok = rows
    w = reference.make_weights(model, engine_seed(seed))
    ref = np.asarray(reference.logits_at(model, w, tokens, rb, rp))
    served = np.where(tok < model["vocab_size"], tok, 0)
    g = gaps(ref, served)
    g[tok >= model["vocab_size"]] = np.inf
    ctl = None
    if quant:
        low = np.asarray(reference.logits_at(model, w, tokens, rb, rp,
                                             quant=quant))
        ctl = gaps(ref, low.argmax(axis=1))
    del w
    return g, ctl


# -- one run ------------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, cfg=None, model: Optional[Dict] = None,
             peaks: Optional[Dict] = None, eng_hook=None,
             control: Optional[str] = None):
    """Build, warm up, drive and check one run; returns (run, checks,
    control gaps). ``cfg``/``model``/``peaks`` replace the chip's only in
    tests at tiny sizes; ``eng_hook`` may break the engine for a test."""
    import jax
    from repro.launch.serve import build_engine

    if cfg is None:
        cfg = program_config(cell.config, cell.reference)
        model = cell.config["model"]
    sizes = cell.config["engine"]
    eng = build_engine(cfg, max_batch=sizes["max_batch"],
                       max_len=sizes["max_len"], seed=engine_seed(seed))
    if eng_hook:
        eng_hook(eng)
    tr = traffic.Traffic(cell.mix, seconds, seed)
    warm_up(eng, traffic.load_pool(cell.mix))
    setup_s = time.perf_counter() - t_start
    clock = CompileClock()
    tracer = _Tracer() if traced else None
    reqs, withdrawn, steps, t_open, t_close = drive(eng, tr, seconds, tracer)
    compiles, compile_s = clock.count, clock.seconds
    jax.block_until_ready(eng.cache)
    stats = jax.devices()[0].memory_stats() or {}
    run = Run(cell=cell.name, model=model, work=cell.work, seed=seed,
              seconds=seconds, setup_s=setup_s, t_open=t_open,
              t_close=t_close, requests=reqs, withdrawn=withdrawn,
              steps=steps, compiles_in_window=compiles,
              compile_s_in_window=compile_s,
              memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)),
              peaks=peaks or {})
    if tracer:
        run.trace = tracer.summary()
        run.trace_overhead_s = tracer.overhead_s
        run.paused = list(tracer.paused)
    del eng
    gc.collect()

    unfinished = sum(1 for r in reqs if not r.done)
    sample = pick_sample(reqs, seed)
    if sample:
        g, ctl = logit_gaps(cell.reference, model, seed,
                            sample_rows(sample), control)
        gap = float(min(g.max(), NO_ANSWER))
        ctl_gap = None if ctl is None else float(ctl.max())
        n_tok = int(len(g))
    else:
        gap, ctl_gap, n_tok = NO_ANSWER, None, 0
    limit = cell.limits["max_logit_gap"]["limit"]
    checks = {"max_logit_gap": {"value": gap, "limit": limit,
                                "tokens": n_tok, "requests": len(sample)},
              "unfinished_requests": {"value": unfinished, "limit": 0}}
    return run, checks, ctl_gap


def is_correct(checks: Dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def control_checks(checks: Dict, control_gap: float) -> Dict:
    """``checks`` with the control's gap in the program's place."""
    return dict(checks, max_logit_gap=dict(checks["max_logit_gap"],
                                           value=control_gap))


def read_metrics(run: Run, metrics: List[Dict]) -> Dict:
    out = {}
    for m in metrics:
        v = metric_reader(m["name"])(run)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_notes(run: Run) -> List[str]:
    """Checks of the run itself, printed before the compared numbers."""
    late = [r.submitted - r.due for r in run.requests]
    ws = run.window_steps()
    adm = sum(1 for s in ws if s.admitted)
    notes = [
        f"run: generator lateness over {len(late)} requests: p50 "
        f"{np.percentile(late, 50) if late else 0:.6f} s, max "
        f"{max(late) if late else 0:.6f} s",
        f"run: compiles inside the window: {run.compiles_in_window} "
        f"({run.compile_s_in_window:.3f} s)",
        f"run: peak device memory {run.memory_peak_bytes} bytes",
        f"run: admitting steps {adm} of {len(ws)} in the window "
        f"({100.0 * adm / max(len(ws), 1):.3f}%)",
        f"run: {len(run.requests)} requests attempted, {run.withdrawn} "
        f"withdrawn from the backlog at the close",
    ]
    if run.trace is not None:
        notes.append(f"run: traced {run.trace.window_s:.3f} s, device busy "
                     f"{run.trace.busy_s:.3f} s, profiler start/stop "
                     f"{run.trace_overhead_s:.3f} s")
    return notes


def result_line(cell: Cell, run: Run, checks: Dict, device: Dict,
                traced: bool) -> Dict:
    """The last line of a run: end-to-end metrics, or per-layer ones with
    the trace's device numbers and breakdown; the compared numbers last."""
    device = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    out = {"correct": is_correct(checks),
           "attempted": len(run.requests),
           "failed": checks["unfinished_requests"]["value"],
           "metrics": read_metrics(run, cell.per_layer if traced
                                   else cell.end_to_end),
           "device": device}
    if traced and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops(
                                run.module_roles()),
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in checks.items()}
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = find_cell(args.workload)
    try:
        device = require_chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = peaks_for(device["kind"])

    run, checks, _ = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=t_start, peaks=peaks)
    out = result_line(cell, run, checks, device, bool(args.trace))
    for line in run_notes(run):
        print(line, file=sys.stderr)
    for k, v in checks.items():
        print(f"check: {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
