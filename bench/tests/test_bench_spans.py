"""The program's spans and counters read by ``bench/tools/program_trace.py``:
innermost-span tagging and the four readings on a synthetic trace, a tiny
traced run on the CPU, and a trace recorded on the chip with the program's
spans (``data/trace_granite_spans.json.gz``: 7 steps of
granite-3-2b.decisions around one 1878-token admission, with the run's
window counters and the loop's record of each step)."""
import gzip
import json
import math
import pathlib

import pytest

from bench import devtrace, harness
from bench.tests.helpers import run_tiny
from bench.tools import program_trace as pt

MS = 1e6
DATA = pathlib.Path(__file__).parent / "data"


def _span(name, t0, dur, **meta):
    return {"name": name, "start_ns": t0 * MS, "dur_ns": dur * MS,
            "meta": meta}


def _device(t0, dur, module="jit_decode_step(3)"):
    return [{"line": line, "name": module if line == devtrace.MODULES_LINE
             else "%fusion.1 = f()", "start_ns": t0 * MS, "dur_ns": dur * MS}
            for line in (devtrace.MODULES_LINE, devtrace.OPS_LINE)]


def _synthetic():
    """Two engine steps of 10 ms: the first admits (the chip busy 1-6 ms,
    then sampling ops at 8.2 and 19.5 ms), the second only decodes (busy
    21-27)."""
    host = [{"name": "engine.step", "start_ns": 0, "dur_ns": 10 * MS},
            {"name": "engine.step", "start_ns": 20 * MS, "dur_ns": 10 * MS}]
    program = [_span("serving.step", 0, 10),
               _span("serving.admit", 0.2, 7.3, rid=4, slot=1, prompt_len=9,
                     bucket=16),
               _span("serving.prefill", 0.2, 0.5),
               _span("serving.install", 0.7, 5.3),
               _span("serving.first_token", 6, 1.5),
               _span("serving.decode", 7.5, 0.5),
               _span("serving.sample", 8, 1),
               _span("serving.step", 20, 10),
               _span("serving.decode", 20, 1),
               _span("serving.sample", 21, 6.5),
               _span("serving.retire", 27.5, 2.5)]
    dev = (_device(1, 5) + _device(8.2, 0.2, "jit_argmax(4)")
           + _device(19.5, 0.5, "jit_argmax(4)") + _device(21, 6))
    return {"planes": {}, "device": dev, "host": host, "program": program}


def test_gaps_take_the_innermost_open_span():
    ev = _synthetic()
    got = pt.idle(ev)
    assert got["window_s"] == pytest.approx(0.030)
    assert got["first_device_event_s"] == pytest.approx(0.001)
    tags = {round(start * 1e3, 3): tag for tag, _, start in got["idle_gaps"]}
    assert tags == {0.0: "serving.prefill", 6.0: "serving.first_token",
                    8.4: "untraced", 20.0: "serving.decode",
                    27.0: "serving.retire"}
    assert got["idle_gaps"][0] == ["untraced", pytest.approx(0.0111),
                                   pytest.approx(0.0084)]
    by = got["idle_s_by_span"]
    assert list(by)[:2] == ["untraced", "serving.retire"]
    assert by["serving.retire"] == pytest.approx(0.003)
    assert sum(by.values()) == pytest.approx(0.030 - 0.0117)
    # the benchmark's own reduction still tags by its own spans alone
    s = devtrace.reduce({k: ev[k] for k in ("planes", "device", "host")})
    assert {g[0] for g in s.idle_gaps} == {"engine.step", "untraced"}
    assert s.modules("decode_step", 5) == ["jit_decode_step(3)"]


def test_innermost_prefers_the_shorter_of_two_that_start_together():
    spans = [_span("serving.step", 20, 10), _span("serving.decode", 20, 1)]
    assert pt.innermost(spans, 20 * MS) == "serving.decode"
    assert pt.innermost(spans, 25 * MS) == "serving.step"
    assert pt.innermost(spans, 31 * MS) == "untraced"


@pytest.mark.parametrize("frames, want", [
    (["engine.step", "serving.step", "serving.decode", "a", "b", "c"],
     ["serving.decode", "a", "b", "c"]),
    (["engine.step", "serving.step", "serving.decode", "b", "c"],
     ["serving.decode", "b", "c"]),
    (["engine.step", "serving.step", "a", "serving.retire", "c"],
     ["a", "serving.retire", "c"]),
    (["engine.step", "a", "b", "c"], ["a", "b", "c"]),
    (["b", "c"], ["b", "c"]),
], ids=["span_further_out", "span_at_the_edge", "span_inside",
        "no_span", "short"])
def test_host_stack_keeps_the_engines_innermost_span(frames, want):
    assert pt.stack_tail(frames, 3) == want


def test_readings_of_spans_and_counters():
    counters = {"serving.steps": 4, "serving.host_reads": 18,
                "serving.prefill_tokens": 900,
                "serving.prefill_padded_tokens": 1024}
    got = pt.readings(_synthetic(), counters)
    assert got == {
        "host_reads_per_step": pytest.approx(4.5),
        "prefill_pad_pct": pytest.approx(100 * 124 / 1024),
        "admit_ms_per_request": pytest.approx(7.3),
        # the second step: 10 ms, 6 of them busy on the device
        "decode_host_gap_ms": pytest.approx(4.0)}
    # no chip in the trace: only the counters' numbers
    ev = dict(_synthetic(), device=[])
    assert set(pt.readings(ev, counters)) == {"host_reads_per_step",
                                              "prefill_pad_pct"}
    assert pt.readings(ev, {}) == {}


def test_fixture_holds_the_steps_around_an_admission():
    ev = _synthetic()
    steps = [{"admitted": [9], "keys": [10]}, {"admitted": [], "keys": [11]}]
    rec = pt.fixture(ev, steps, {"serving.steps": 2}, n=2)
    assert rec["steps"] == steps and rec["counters"] == {"serving.steps": 2}
    assert len(rec["program"]) == len(ev["program"])
    assert pt.fixture(ev, steps[:1], {}, n=2) is None


def test_traced_tiny_run_keeps_the_programs_spans(monkeypatch):
    """On the CPU: the engine's spans nest inside the loop's steps, the
    counters span the window, and only the counters' readings exist."""
    monkeypatch.setattr(harness, "_Tracer", pt.KeepingTracer)
    run, checks, _ = run_tiny("granite-3-2b.decisions", seconds=2.0,
                              traced=True)
    assert harness.is_correct(checks)
    tr = pt.KeepingTracer.last
    ev = tr.events
    from repro.core import profiling
    c = profiling.delta(tr.at_open, tr.at_close)
    steps = run.traced_steps()
    names = pt.host_time(ev)
    assert names["serving.step"][0] == names["engine.step"][0] == len(steps)
    assert names["serving.admit"][0] == sum(len(s.admitted) for s in steps)
    admits = [p for p in ev["program"] if p["name"] == "serving.admit"]
    assert {p["meta"]["prompt_len"] for p in admits} == \
        {n for s in steps for n in s.admitted}
    # the window's counters: reads are one per admission, and one plus the
    # decoded slots per step
    ws = run.window_steps()
    assert c["serving.steps"] == len(ws)
    assert c["serving.host_reads"] == sum(
        len(s.admitted) + (1 + len(s.keys) if s.keys else 0) for s in ws)
    assert set(pt.readings(ev, c)) == {"host_reads_per_step",
                                       "prefill_pad_pct"}
    # the host events open in the longest gaps hold the engine's step
    assert tr.stacks and all(tr.stacks)
    assert any(n.startswith("serving.") for st in tr.stacks for n in st)


def test_step_walls_by_slots():
    steps = [harness.Step(0.0, 0.020, [], [5, 6], False, True),
             harness.Step(0.0, 0.024, [], [5, 6], False, True),
             harness.Step(0.0, 0.023, [], [5, 6], True, True),
             harness.Step(0.0, 0.200, [9], [5, 6], True, True),
             harness.Step(0.0, 0.030, [], [5, 6, 7], False, True),
             harness.Step(0.0, 0.500, [], [5, 6], False, False)]
    run = harness.Run(cell="c", model={}, work=None, seed=1, seconds=1.0,
                      setup_s=1.0, t_open=0.0, t_close=1.0, requests=[],
                      withdrawn=0, steps=steps, compiles_in_window=0,
                      compile_s_in_window=0.0, memory_peak_bytes=0, peaks={})
    got = pt.step_walls(run)
    assert got == {2: [pytest.approx(23.0), pytest.approx(22.0), 1, 2],
                   3: [None, pytest.approx(30.0), 0, 1]}


def _recorded():
    with gzip.open(DATA / "trace_granite_spans.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_steps_trace_under_their_names():
    """The jitted steps are found by name, whatever the execution counts."""
    s = devtrace.reduce(_recorded())
    [dec] = s.modules("decode_step", 99)
    [pre] = s.modules("prefill_step", 99)
    assert dec.startswith("jit_decode_step(") and s.module_n[dec] == 7
    assert pre.startswith("jit_prefill_step(") and s.module_n[pre] == 1
    assert not any(k.startswith("jit__unknown") for k in s.module_s)


def test_recorded_spans_nest_and_carry_the_request():
    ev = _recorded()
    names = pt.host_time(ev)
    assert names["engine.step"][0] == names["serving.step"][0] == 7
    for n in ("serving.decode", "serving.sample", "serving.retire"):
        assert names[n][0] == 7
    admit, = [p for p in ev["program"] if p["name"] == "serving.admit"]
    assert admit["meta"]["prompt_len"] == 1878 == ev["steps"][3]["admitted"][0]
    assert admit["meta"]["bucket"] == 2048
    assert set(admit["meta"]) == {"rid", "slot", "prompt_len", "bucket"}
    a, b = admit["start_ns"], admit["start_ns"] + admit["dur_ns"]
    kids = [p["name"] for p in ev["program"]
            if a <= p["start_ns"] and p["start_ns"] + p["dur_ns"] <= b
            and p is not admit]
    assert kids == ["serving.prefill", "serving.install",
                    "serving.first_token"]


def test_recorded_trace_through_all_nine_readers():
    """The benchmark's five per-layer readers and the tool's four, on the
    recorded steps: every share within 100%, each number near what the
    chip showed for the whole window."""
    ev = _recorded()
    s = devtrace.reduce(ev)
    steps = [harness.Step(0, 0, st["admitted"], st["keys"], True, True)
             for st in ev["steps"]]
    cell = harness.find_cell("granite-3-2b.decisions")
    run = harness.Run(
        cell="x", model=cell.config["model"], work=cell.work, seed=0,
        seconds=1, setup_s=1, t_open=0, t_close=1, requests=[], withdrawn=0,
        steps=steps, compiles_in_window=0, compile_s_in_window=0,
        memory_peak_bytes=0, trace=s, peaks=harness.peaks_for("TPU v5 lite"))
    got = {k: v["value"] for k, v in
           harness.read_metrics(run, cell.per_layer).items()}
    # no requests in the record: the host-clock reader reads nothing
    assert set(got) == {"prefill_mfu_pct", "decode_roofline_pct",
                        "decode_mfu_pct", "device_idle_pct"}
    prog = pt.readings(ev, ev["counters"])
    assert set(prog) == {"host_reads_per_step", "prefill_pad_pct",
                         "admit_ms_per_request", "decode_host_gap_ms"}
    for v in list(got.values()) + list(prog.values()):
        assert math.isfinite(v) and v > 0
    for k in ("prefill_mfu_pct", "decode_roofline_pct", "decode_mfu_pct",
              "device_idle_pct", "prefill_pad_pct"):
        assert ({**got, **prog})[k] < 100
    assert 20 < got["prefill_mfu_pct"] < 50
    assert 20 < got["decode_roofline_pct"] < 60
    # ~1 + 4.2 slots a step; the 112-prompt pool in the 2048 bucket
    assert 4.5 < prog["host_reads_per_step"] < 6.5
    assert 5 < prog["prefill_pad_pct"] < 15
    # the admission holds a ~160 ms prefill; a decode step ~20 ms of ~28
    assert 150 < prog["admit_ms_per_request"] < 250
    assert 1 < prog["decode_host_gap_ms"] < 20


def test_recorded_gaps_name_the_programs_phases():
    ev = _recorded()
    got = pt.idle(ev)
    assert got["idle_gaps"][0][0] == "serving.admit"
    assert {g[0] for g in got["idle_gaps"]} <= {
        "serving.admit", "serving.first_token", "serving.retire",
        "serving.sample", "serving.decode"}
    by = got["idle_s_by_span"]
    assert max(by, key=by.get) == "serving.retire"
    # the attribution splits the idle time that the benchmark's own
    # reduction puts down to engine.step, and changes none of it
    s = devtrace.reduce(ev)
    assert sum(by.values()) == pytest.approx(s.window_s - s.busy_s)
    assert {g[0] for g in s.idle_gaps} == {"engine.step"}
