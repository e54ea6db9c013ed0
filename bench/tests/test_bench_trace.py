"""The trace reduction: interval arithmetic, and a trace recorded on the
chip (``data/trace_granite_decisions.json.gz``: 7 steps of
granite-3-2b.decisions, one of which prefills a 2048-token prompt)."""
import gzip
import json
import math
import pathlib

import pytest

from bench import devtrace, harness

DATA = pathlib.Path(__file__).parent / "data"
MS = 1e6


def _recorded():
    with gzip.open(DATA / "trace_granite_decisions.json.gz", "rt") as f:
        return json.load(f)


def test_interval_arithmetic():
    u = devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert devtrace.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert devtrace.subtract([(0, 10)], [(2, 3), (5, 8)]) == \
        [(0, 2), (3, 5), (8, 10)]
    assert devtrace.length(u) == 6


def _synthetic():
    ms = 1e6
    host = [{"name": "engine.step", "start_ns": 0, "dur_ns": 10 * ms},
            {"name": "bench.wait_arrival", "start_ns": 10 * ms,
             "dur_ns": 10 * ms},
            {"name": "engine.step", "start_ns": 20 * ms, "dur_ns": 10 * ms}]
    dev = []
    for t0, name, dur in [(1, "jit__unknown(1)", 6), (21, "jit__unknown(1)", 6),
                          (8, "jit_argmax(2)", 1), (28, "jit_argmax(2)", 1)]:
        dev.append({"line": devtrace.MODULES_LINE, "name": name,
                    "start_ns": t0 * ms, "dur_ns": dur * ms})
        dev.append({"line": devtrace.OPS_LINE, "name": "%while.1 = (...)",
                    "start_ns": t0 * ms, "dur_ns": dur * ms})
        dev.append({"line": devtrace.OPS_LINE, "name": "%fusion.2 = f()",
                    "start_ns": t0 * ms, "dur_ns": dur * ms / 2})
    return {"planes": {}, "device": dev, "host": host}


def test_synthetic_reduction():
    s = devtrace.reduce(_synthetic())
    assert s.window_s == pytest.approx(0.030)
    assert s.busy_s == pytest.approx(0.014)
    assert s.work_s == pytest.approx(0.020)
    assert s.idle_with_work_s == pytest.approx(0.006)
    assert s.modules("decode_step", 2) == ["jit__unknown(1)"]
    assert s.modules("prefill_step", 3) == []
    # the 10 ms wait is the longest gap; the container while is left out
    assert s.idle_gaps[0] == ["bench.wait_arrival", pytest.approx(0.012)]
    ops = s.top_ops({"jit__unknown(1)": "decode_step"})
    assert ops[0] == ["decode_step:%fusion.2", pytest.approx(0.006)]
    assert all("%while" not in name for name, _ in ops)


def test_recorded_trace():
    ev = _recorded()
    s = devtrace.reduce(ev)
    steps = [h for h in ev["host"] if h["name"] == "engine.step"]
    assert len(steps) == 7
    assert 0 < s.busy_s < s.window_s == pytest.approx(s.work_s)
    assert 0.5 < s.busy_s / s.window_s < 1.0
    [dec] = s.modules("decode_step", 7)
    [pre] = s.modules("prefill_step", 1)
    assert dec and pre and dec != pre and dec.startswith("jit__unknown")
    # one decode per step at ~20 ms, one 2048-token prefill at ~160 ms
    assert 0.015 < s.module_s[dec] / 7 < 0.030
    assert 0.10 < s.module_s[pre] < 0.25
    assert s.module_s[pre] + s.module_s[dec] < s.busy_s
    assert {g[0] for g in s.idle_gaps} <= set(devtrace.SPANS) | {"untraced"}
    ops = s.top_ops({dec: "decode_step", pre: "prefill_step"})
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1] > 0
    assert {o[0].split(":")[0] for o in ops} <= {"decode_step",
                                                  "prefill_step"}
    # a window that does not show the loop's steps one for one reads nothing
    assert s.modules("decode_step", 8) != [dec]


def _granite_run(trace, steps):
    """A run of granite-3-2b.decisions on a v5e that holds ``trace`` and
    the loop's ``steps``, as the per-layer readers take it."""
    cell = harness.find_cell("granite-3-2b.decisions")
    return cell, harness.Run(
        cell="x", model=cell.config["model"], work=cell.work, seed=0,
        seconds=1, setup_s=1, t_open=0, t_close=1, requests=[], withdrawn=0,
        steps=steps, compiles_in_window=0, compile_s_in_window=0,
        memory_peak_bytes=0, trace=trace,
        peaks=harness.peaks_for("TPU v5 lite"))


def _recorded_steps():
    """The loop's steps of the recorded trace, reconstructed as 8 slots of
    ~2 000-token contexts with one 1800-token admission."""
    return [harness.Step(0, 0, [1800] if i == 3 else [], [2000] * 8, True,
                         True) for i in range(7)]


def test_recorded_trace_metrics_stay_under_their_peaks():
    """Through the readers, with the loop's steps reconstructed: every
    share stays within 100%."""
    cell, run = _granite_run(devtrace.reduce(_recorded()), _recorded_steps())
    got = harness.read_metrics(run, cell.per_layer)
    assert set(got) == {"prefill_mfu_pct", "decode_roofline_pct",
                        "decode_mfu_pct", "device_idle_pct"}
    for v in got.values():
        assert 0 < v["value"] < 100 and math.isfinite(v["value"])
    # 11 TFLOP in ~160 ms, 7 GB in ~20 ms: both near a third of the peak
    assert 20 < got["prefill_mfu_pct"]["value"] < 50
    assert 20 < got["decode_roofline_pct"]["value"] < 60


PINNED = {
    "trace_granite_decisions": {
        "prefill_mfu_pct": 29.609285169893592,
        "decode_roofline_pct": 38.6809813415343,
        "decode_mfu_pct": 1.1540367181114823,
        "device_idle_pct": 18.066216351064185},
    "trace_granite_spans": {
        "prefill_mfu_pct": 30.958507089015487,
        "decode_roofline_pct": 35.03569664449864,
        "decode_mfu_pct": 0.6555883953747281,
        "device_idle_pct": 17.45291105359168},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_recorded_trace_reads_as_it_did_with_one_module_a_step(name):
    """Each step of a recorded trace ran as one program, so summing over a
    step's programs reads what taking its one program read: the values
    were read from the recorded traces before the sum. The host-clock
    reader ``queue_wait_p90_s`` reads nothing, as no record holds
    requests."""
    with gzip.open(DATA / f"{name}.json.gz", "rt") as f:
        ev = json.load(f)
    steps = ([harness.Step(0, 0, st["admitted"], st["keys"], True, True)
              for st in ev["steps"]] if "steps" in ev
             else _recorded_steps())
    cell, run = _granite_run(devtrace.reduce(ev), steps)
    got = {k: v["value"] for k, v in
           harness.read_metrics(run, cell.per_layer).items()}
    assert got == {k: pytest.approx(v, rel=1e-9)
                   for k, v in PINNED[name].items()}


def _prefill_trace(programs, decodes=3):
    """``decodes`` engine steps of 20 ms, each with one 5 ms
    ``jit_decode_step(9)``, then one step a prefill: a module of each
    ``(name, ms)`` of ``programs`` holding one op ``%fusion.1``."""
    host, dev = [], []

    def run(name, t0, dur):
        for line, ev in ((devtrace.MODULES_LINE, name),
                         (devtrace.OPS_LINE, "%fusion.1 = f()")):
            dev.append({"line": line, "name": ev, "start_ns": t0 * MS,
                        "dur_ns": dur * MS})

    t = 0.0
    for _ in range(decodes):
        host.append({"name": "engine.step", "start_ns": t * MS,
                     "dur_ns": 20 * MS})
        run("jit_decode_step(9)", t + 1, 5)
        t += 20
    for name, ms in programs:
        host.append({"name": "engine.step", "start_ns": t * MS,
                     "dur_ns": (ms + 2) * MS})
        run(name, t + 1, ms)
        t += ms + 2
    return {"planes": {}, "device": dev, "host": host}


ONE = [("jit_prefill_step(1)", 10)] * 3
THREE = [("jit_prefill_step(1)", 5), ("jit_prefill_step(2)", 10),
         ("jit_prefill_step(3)", 15)]


@pytest.mark.parametrize("programs, admitted, decoded, want_s", [
    (ONE, [1000, 1500, 2000], 3, 0.030),
    (THREE, [1000, 1500, 2000], 3, 0.030),
    (THREE, [1000, 1500], 3, None),
    (THREE, [1000, 1500, 2000, 500], 3, None),
    (THREE, [1000, 1500, 2000], 4, 0.030),
], ids=["one_program_three_runs", "three_programs_one_run_each",
        "fewer_admissions_than_runs", "more_admissions_than_runs",
        "decodes_not_one_for_one"])
def test_a_step_reads_over_every_program_it_compiled(programs, admitted,
                                                      decoded, want_s):
    """A prefill compiled at three shapes reads the device time and MFU of
    one program run three times for the same total time; where the
    programs' runs are not the loop's admissions one for one it reads
    nothing, and the breakdown shows each of the step's ops once."""
    from bench.stats import share_pct

    s = devtrace.reduce(_prefill_trace(programs))
    steps = [harness.Step(0, 0, admitted if i == 0 else [], [2000] * 8,
                          True, True) for i in range(decoded)]
    cell, run = _granite_run(s, steps)
    assert set(run.module_roles().values()) == {"decode_step",
                                                "prefill_step"}
    got = {k: v["value"] for k, v in
           harness.read_metrics(run, cell.per_layer).items()}
    if want_s is None:
        assert run.device_seconds("prefill_step") is None
        assert "prefill_mfu_pct" not in got
    else:
        assert run.device_seconds("prefill_step") == pytest.approx(want_s)
        flops = sum(cell.work.prefill_flops(run.model, n) for n in admitted)
        assert got["prefill_mfu_pct"] == pytest.approx(share_pct(
            flops, want_s * run.peaks["bf16_flops_per_s"]))
    if decoded == 3:
        assert run.device_seconds("decode_step") == pytest.approx(0.015)
    else:
        assert run.device_seconds("decode_step") is None
        assert "decode_roofline_pct" not in got
    ops = s.top_ops(run.module_roles())
    assert len(ops) == 2 and dict(ops) == {
        "prefill_step:%fusion.1": pytest.approx(0.030),
        "decode_step:%fusion.1": pytest.approx(0.015)}
