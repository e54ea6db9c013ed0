"""The trace reduction: interval arithmetic, and a trace recorded on the
chip (``data/trace_granite_decisions.json.gz``: 7 steps of
granite-3-2b.decisions, one of which prefills a 2048-token prompt)."""
import gzip
import json
import pathlib

import pytest

from bench import devtrace

DATA = pathlib.Path(__file__).parent / "data"


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
    assert s.role("decode_step", 2) == "jit__unknown(1)"
    assert s.role("prefill_step", 3) is None
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
    dec = s.role("decode_step", 7)
    pre = s.role("prefill_step", 1)
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
    assert s.role("decode_step", 8) != dec


def test_recorded_trace_metrics_stay_under_their_peaks():
    """Through the readers, with the loop's steps reconstructed as 8 slots
    of ~2 000-token contexts: every share stays within 100%."""
    import math

    from bench import harness
    from bench.harness import Run, Step

    s = devtrace.reduce(_recorded())
    steps = [Step(0, 0, [1800] if i == 3 else [], [2000] * 8, True, True)
             for i in range(7)]
    cell = harness.find_cell("granite-3-2b.decisions")
    run = Run(cell="x", model=cell.config["model"], work=cell.work, seed=0,
              seconds=1, setup_s=1, t_open=0, t_close=1, requests=[],
              withdrawn=0, steps=steps, compiles_in_window=0,
              compile_s_in_window=0, memory_peak_bytes=0, trace=s,
              peaks=harness.peaks_for("TPU v5 lite"))
    got = harness.read_metrics(run, cell.per_layer)
    assert set(got) == {"prefill_mfu_pct", "decode_roofline_pct",
                        "decode_mfu_pct", "device_idle_pct"}
    for v in got.values():
        assert 0 < v["value"] < 100 and math.isfinite(v["value"])
    # 11 TFLOP in ~160 ms, 7 GB in ~20 ms: both near a third of the peak
    assert 20 < got["prefill_mfu_pct"]["value"] < 50
    assert 20 < got["decode_roofline_pct"]["value"] < 60
