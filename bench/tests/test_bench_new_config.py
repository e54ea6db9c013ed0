"""A configuration is added through new files alone: a copy of
``BENCHMARK.json`` beside a configuration file that names its own reference
and work modules, its limits and a cell of it, run at the tiny CPU size to
the result line. Nothing the benchmark already has is edited."""
import gzip
import json
import pathlib
import time

import pytest

from bench import harness, reference, work
from bench.tests.helpers import PEAKS, tiny

DATA = pathlib.Path(__file__).parent / "data"

BASE_CELL = "granite-3-2b.decisions"
NAME = "granite-3-2b-rec"
CELL = f"{NAME}.decisions"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
MODULES = {"reference": "bench/tests/recording_reference.py",
           "work": "bench/tests/recording_work.py"}


def _checkout(root, leave_out=None):
    """``root`` as a checkout holding a copy of ``BENCHMARK.json`` with one
    more configuration and cell, and the new files they name; the
    configuration file lacks the key ``leave_out``."""
    bench = json.loads(harness.BENCHMARK_JSON.read_text())
    base = json.loads((harness.BENCH / "configs"
                       / "granite-3-2b.json").read_text())
    config = dict(base, name=NAME, **MODULES)
    config.pop(leave_out, None)
    new = {f"bench/configs/{NAME}.json": json.dumps(config),
           f"bench/limits/{CELL}.json":
               (harness.BENCH / "limits" / f"{BASE_CELL}.json").read_text()}
    for path in MODULES.values():
        new[path] = (harness.ROOT / path).read_text()
    bench["configs"].append({"name": NAME, "source": base["source"],
                             "file": f"bench/configs/{NAME}.json",
                             "reduced": [], "why": "a test's configuration"})
    bench["workloads"].append({"name": CELL, "config": NAME,
                               "traffic": "decisions", "chips": 1,
                               "why": "a test's cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if BASE_CELL in m.get("workloads", []):
            m["workloads"] = m["workloads"] + [CELL]
    new["BENCHMARK.json"] = json.dumps(bench)
    for path, text in new.items():
        (root / path).parent.mkdir(parents=True, exist_ok=True)
        (root / path).write_text(text)
    return root / "BENCHMARK.json"


@pytest.fixture
def recorded_trace(monkeypatch):
    """The profiler held still and the chip's recorded trace
    (``data/trace_granite_spans.json.gz``) read in place of the CPU's, which
    holds no device ops for the trace readers; returns the record."""
    import jax
    with gzip.open(DATA / "trace_granite_spans.json.gz", "rt") as f:
        ev = json.load(f)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(harness.devtrace, "extract", lambda d: ev)
    return ev


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_config_added_with_new_files_only(tmp_path, request, traced):
    rec = request.getfixturevalue("recorded_trace") if traced else None
    cell, cfg, model = tiny(CELL, bench_json=_checkout(tmp_path))
    # the modules the configuration names, and no file in their place
    for key, mod in (("reference", cell.reference), ("work", cell.work)):
        assert mod.__file__ == str(tmp_path / MODULES[key])
        assert mod is not reference and mod is not work
    assert not hasattr(harness, "reference")
    assert not hasattr(harness, "work")
    # the harness checks the file's model block (it raises where the
    # program's differs) through the named reference
    cell.reference.CALLS.clear()
    harness.program_config(cell.config, cell.reference)
    assert cell.reference.CALLS == ["model_block"]

    run, checks, ctl = harness.run_cell(
        cell, 3, 1.5, traced, t_start=time.perf_counter(), cfg=cfg,
        model=model, peaks=PEAKS, control="fp8")
    if traced:
        # the recorded trace is read against the loop's record of its own
        # steps, which its modules' executions match one for one
        run.steps = [harness.Step(0, 0, st["admitted"], st["keys"], True,
                                  True) for st in rec["steps"]]
    line = harness.result_line(cell, run, checks, DEVICE, traced)
    assert line["correct"] is True and line["attempted"] >= 1
    assert run.work is cell.work
    # the named reference computed max_logit_gap and its control
    assert checks["max_logit_gap"]["tokens"] > 0 and ctl is not None
    assert {"model_block", "make_weights", ("logits_at", None),
            ("logits_at", "fp8")} <= set(cell.reference.CALLS)
    if traced:
        # the named work counts fed the model readers
        assert {"decode_roofline_pct", "decode_mfu_pct",
                "prefill_mfu_pct"} <= set(line["metrics"])
        assert {"decode_flops", "decode_bytes",
                "prefill_flops"} <= set(cell.work.CALLS)
    else:
        assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert cell.work.CALLS == []


@pytest.mark.parametrize("key", ["reference", "work"])
def test_config_without_its_modules_is_refused(tmp_path, key):
    bench_json = _checkout(tmp_path, leave_out=key)
    with pytest.raises(ValueError, match=repr(key)):
        harness.find_cell(CELL, bench_json)
