"""Whole runs at a tiny size on the CPU: the last line, the refusal without
a chip, and the control."""
import dataclasses
import json
import time
import types

import numpy as np
import pytest

from bench import harness, traffic
from bench.tests.helpers import cell_for, run_tiny

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_refuses_the_cpu(capsys):
    rc = harness.main(["--workload", "granite-3-2b.decisions", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == "" and "TPU" in out.err


@pytest.mark.parametrize("cell", ["granite-3-2b.decisions",
                                  "granite-3-2b.cot-backlog"])
def test_last_line_shape(cell):
    run, checks, _ = run_tiny(cell, seconds=2.0)
    c = cell_for(cell)
    line = harness.result_line(c, run, checks, DEVICE, False)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["device"]["memory_peak_bytes"] == run.memory_peak_bytes
    for k, v in line["checks"].items():
        assert set(v) == {"value", "limit"}
    assert json.loads(json.dumps(line)) == line
    assert run.compiles_in_window == 0


def test_traced_line_leaves_out_what_it_cannot_read():
    """On the CPU there is no TPU plane, so the trace-read metrics stay out
    and the host-clock one is there."""
    cell = "granite-3-2b.decisions"
    run, checks, _ = run_tiny(cell, seconds=2.0, traced=True)
    line = harness.result_line(harness.find_cell(cell), run, checks, DEVICE,
                               True)
    assert set(line["metrics"]) == {"queue_wait_p90_s"}


def test_control_reads_wider_than_the_program():
    """The float8 control of the reference puts other tokens first, where
    the program's own served tokens sit at the reference's best; in the
    program's place, the control is not correct at the cell's limit."""
    prog, ctl = [], []
    for seed in (11, 12, 13):
        run, checks, c = run_tiny("granite-3-2b.cot-backlog", seed=seed,
                                  seconds=1.5, control="fp8")
        prog.append(checks["max_logit_gap"]["value"])
        ctl.append(c)
        assert harness.is_correct(checks), checks
        assert not harness.is_correct(harness.control_checks(checks, c))
    assert max(prog) < min(ctl)


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_control_is_not_correct_in_the_decisions_cell(seed):
    run, checks, c = run_tiny("granite-3-2b.decisions", seed=seed,
                              seconds=1.5, control="fp8")
    assert harness.is_correct(checks), checks
    assert not harness.is_correct(harness.control_checks(checks, c)), c


def test_queue_wait_leaves_out_the_benchmarks_own_pause():
    """A request queued across the profiler's stop waits only for the
    scheduler's part."""
    class H:
        done = True

    reqs = [harness.Req(None, 10.0 + i, 10.0 + i, H(), admit_t=10.5 + i)
            for i in range(9)]
    reqs.append(harness.Req(None, 20.0, 20.0, H(), admit_t=33.0))
    run = harness.Run(cell="c", model={}, work=None, seed=1, seconds=10.0,
                      setup_s=1.0, t_open=10.0, t_close=20.5, requests=reqs,
                      withdrawn=0, steps=[], compiles_in_window=0,
                      compile_s_in_window=0.0, memory_peak_bytes=1, peaks={},
                      paused=[(20.5, 32.5)])
    assert run.unpaused(20.0, 33.0) == pytest.approx(1.0)
    assert run.unpaused(10.0, 10.5) == pytest.approx(0.5)
    read = harness.metric_reader("queue_wait_p90_s")
    assert 0.5 <= read(run) <= 1.0
    assert read(dataclasses.replace(run, paused=[])) > 1.0


def test_tracer_pauses_cover_its_start_and_stop(monkeypatch):
    """Both the profiler's start inside the window and its stop at the
    close hold the loop; each is a pause that queue waits leave out."""
    import shutil
    import time

    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: time.sleep(0.05))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: time.sleep(0.03))
    tr = harness._Tracer()
    tr.start()
    tr.stop()
    (a0, a1), (b0, b1) = tr.paused
    assert a1 - a0 >= 0.05 and b1 - b0 >= 0.03 and a1 <= b0
    assert tr.overhead_s == pytest.approx((a1 - a0) + (b1 - b0))
    shutil.rmtree(tr.dir)


def test_pick_sample_holds_the_longest():
    class H:
        def __init__(self, n):
            self.out_ids, self.done, self.prompt_ids = [1] * n, True, [1]

    class S:
        def __init__(self, i):
            self.idx = i

    reqs = [harness.Req(S(i), 0.0, 0.0, H(n)) for i, n in
            enumerate([50, 300, 120, 80, 90])]
    picked = harness.pick_sample(reqs, 5)
    assert picked[0].handle.out_ids == [1] * 300
    assert sum(len(r.handle.out_ids) for r in picked) >= \
        min(harness.SAMPLE_TOKENS, 640)
    assert len(picked) <= harness.SAMPLE_MAX_REQUESTS
    assert picked == harness.pick_sample(reqs, 5)
    assert np.all([r.done for r in picked])


class _SlowEngine:
    """One slot; a request holds it for ``steps`` steps of ``dt`` seconds."""
    max_batch = 1

    def __init__(self, steps=40, dt=0.004):
        self.steps, self.dt = steps, dt
        self.waiting, self.slots, self.sent = [], [None], []

    def submit(self, prompt, max_new_tokens):
        h = types.SimpleNamespace(prompt_ids=[1, 2], out_ids=[], done=False,
                                  first_token_at=None,
                                  sent_at=time.perf_counter())
        self.waiting.append(h)
        self.sent.append(h)
        return h

    def step(self):
        time.sleep(self.dt)
        h = self.slots[0]
        if h is None and self.waiting:
            h = self.slots[0] = self.waiting.pop(0)
            h.first_token_at = time.perf_counter()
        if h is not None:
            h.out_ids.append(0)
            if len(h.out_ids) >= self.steps:
                h.done, self.slots[0] = True, None


def test_drive_measures_the_window_between_a_lead_in_and_the_rest():
    """Requests due before the open and after the close load the engine
    and are not returned; the schedule goes on past the close while the
    window's requests drain, and the engine is left empty."""
    mix = dict(traffic.load_mix("decisions"), lead_in_s=0.4,
               arrivals={"process": "poisson", "rate_per_s": 8.0})
    tr = traffic.Traffic(mix, 1.0, 5)
    eng = _SlowEngine()
    reqs, withdrawn, steps, t_open, t_close = harness.drive(eng, tr, 1.0)
    assert [r.spec for r in reqs] == tr.specs and withdrawn == 0
    assert all(r.done for r in reqs)
    early = [h for h in eng.sent if h.sent_at < t_open]
    assert len(early) == len(tr.lead_in) > 0
    late = [r for r in reqs if r.handle.sent_at >= t_close]
    assert all(r.due < t_close for r in late)
    rest = eng.sent[len(tr.lead_in) + len(reqs):]
    assert rest, "the drain outlasts the next arrival at this load"
    assert all(h.sent_at >= t_close for h in rest)
    assert not eng.waiting and eng.slots == [None]
    assert any(not s.in_window for s in steps if s.t0 < t_open)
    assert all(s.in_window == (t_open <= s.t0 < t_close) for s in steps)
