"""The serving engine's spans and counters (``core/profiling``): spans in
the JAX profiler's own trace, nested as the engine's phases nest; counters
that count each blocking device-to-host read; backend compiles counted by
one listener; ``stats()`` read from the counters and the request stamps."""
import dataclasses
import functools
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import profiling
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.models import Init, init_model, unbox
from repro.serving import ServingEngine

PROMPTS = ("alpha", "a much longer prompt about satellites", "geo")
STEP_PARTS = {"serving.admit", "serving.decode", "serving.sample",
              "serving.retire"}
ADMIT_PARTS = ("serving.prefill", "serving.install", "serving.first_token")


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(get_config("dcache-agent-150m").reduced(),
                              vocab_size=512)
    params, _ = unbox(init_model(Init(jax.random.PRNGKey(0),
                                      dtype=cfg.jnp_dtype), cfg))
    return cfg, params


def _engine(model, max_batch=2):
    cfg, params = model
    return ServingEngine(cfg, params, max_batch=max_batch, max_len=64)


def _serve(eng, max_new_tokens=4):
    reqs = [eng.submit(p, max_new_tokens=max_new_tokens) for p in PROMPTS]
    eng.run_until_done()
    return reqs


def _host_spans(trace_dir):
    """``serving.*`` events of the trace's host planes, as (plane, line,
    name, start, end, stats)."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith("serving."):
                    out.append((plane.name, ln.name, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _inside(inner, outer):
    return outer[3] <= inner[3] and inner[4] <= outer[4]


def test_spans_nest_on_the_profilers_host_plane(model, tmp_path):
    eng = _engine(model)
    _serve(eng)                     # every shape compiled outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        reqs = _serve(eng)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    assert len({(s[0], s[1]) for s in spans}) == 1     # one thread's line
    by = {}
    for s in spans:
        by.setdefault(s[2], []).append(s)
    steps = by["serving.step"]
    assert steps
    assert set(by) == {"serving.step"} | STEP_PARTS | set(ADMIT_PARTS)
    for name in STEP_PARTS:
        for s in by[name]:
            assert sum(_inside(s, st) for st in steps) == 1, name
    admits = by["serving.admit"]
    assert sorted(a[5]["rid"] for a in admits) == [r.rid for r in reqs]
    for a in admits:
        r = next(r for r in reqs if r.rid == a[5]["rid"])
        assert a[5]["prompt_len"] == len(r.prompt_ids)
        assert a[5]["bucket"] >= a[5]["prompt_len"]
        assert 0 <= a[5]["slot"] < eng.max_batch
        kids = [s for s in spans if s[2] in ADMIT_PARTS and _inside(s, a)]
        assert [s[2] for s in sorted(kids, key=lambda s: s[3])] == \
            list(ADMIT_PARTS)
    for name in ("serving.decode", "serving.sample", "serving.retire"):
        assert not any(_inside(s, a) for s in by[name] for a in admits)


def test_span_records_nothing_without_a_trace():
    assert profiling.span("serving.step") is profiling._NO_SPAN
    assert profiling.span("serving.admit", rid=1) is profiling._NO_SPAN


def test_host_reads_count_each_blocking_read(model):
    """The first token of each admission, the sampled ids of each decode
    step and the position of each slot it decoded: nothing more."""
    eng = _engine(model)
    reqs = [eng.submit(p, max_new_tokens=5) for p in PROMPTS]
    before = profiling.snapshot()
    expected = steps = decodes = slots = 0
    while eng.waiting or any(eng.slots):
        admitted = sum(1 for r in reqs if r.admitted_at is None)
        active = eng.step()
        admitted -= sum(1 for r in reqs if r.admitted_at is None)
        expected += admitted + (1 + active if active else 0)
        steps += 1
        decodes += bool(active)
        slots += active
    got = profiling.delta(before, profiling.snapshot())
    assert got["serving.host_reads"] == expected == eng.counters[
        "serving.host_reads"]
    assert got["serving.steps"] == steps
    assert got["serving.decode_steps"] == decodes
    assert got["serving.slot_steps"] == slots
    assert got["serving.admissions"] == len(reqs)
    assert got["serving.tokens_out"] == sum(len(r.out_ids) for r in reqs)
    assert got["serving.prefill_tokens"] == sum(len(r.prompt_ids)
                                                for r in reqs)
    # prompts of 6, 32 (cut to max_len // 2) and 4 tokens: buckets 8, 32, 8
    assert [len(r.prompt_ids) for r in reqs] == [6, 32, 4]
    assert got["serving.prefill_padded_tokens"] == 8 + 32 + 8


@pytest.mark.parametrize("tpu,kernel", [(False, 0), (True, 1)],
                         ids=["cpu", "tpu"])
def test_prefill_kernel_counts_the_admissions_that_run_it(
        model, monkeypatch, tpu, kernel):
    """``serving.prefill_kernel`` counts an admission by the test that
    dispatches its prefill: none on the CPU; on a TPU, the one whose bucket
    (128) the kernel takes and not the one of 8 (seen as a TPU here, with
    the kernel interpreted)."""
    if tpu:
        monkeypatch.setattr(ops, "_interpret", lambda: False)
        monkeypatch.setattr(ops, "flash_attention", functools.partial(
            flash_attention, interpret=True))
    cfg, params = model
    eng = ServingEngine(cfg, params, max_batch=2, max_len=256)
    reqs = [eng.submit(p, max_new_tokens=2) for p in ("x" * 100, "geo")]
    before = profiling.snapshot()
    eng.run_until_done()
    got = profiling.delta(before, profiling.snapshot())
    assert got["serving.admissions"] == len(reqs)
    assert got.get("serving.prefill_kernel", 0) == kernel
    assert eng.counters["serving.prefill_kernel"] == kernel
    assert "serving.prefill_kernel" in eng.counters
    assert all(r.done for r in reqs)


def test_compiles_are_counted_by_one_listener():
    profiling.count_compiles()
    profiling.count_compiles()
    x = jnp.arange(7.0).block_until_ready()
    before = profiling.snapshot()
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    got = profiling.delta(before, profiling.snapshot())
    assert got["jax.compiles"] == 1 and got["jax.compile_s"] > 0


def test_jitted_steps_trace_under_their_names(model):
    eng = _engine(model)
    tokens = jnp.zeros((eng.max_batch, 1), jnp.int32)
    text = eng._decode.lower(eng.params, tokens, eng.cache).as_text()
    assert text.startswith("module @jit_decode_step ")
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    text = eng._prefill_fn(8).lower(
        eng.params, batch, true_lens=jnp.asarray([3], jnp.int32)).as_text()
    assert text.startswith("module @jit_prefill_step ")


def test_stats_reads_the_counters_and_stamps(model):
    eng = _engine(model)
    assert eng.stats() == {"finished": 0}
    reqs = _serve(eng)
    s = eng.stats()
    assert s["finished"] == len(reqs)
    for r in reqs:
        assert r.submitted_at <= r.admitted_at <= r.first_token_at
    assert 0 <= s["queue_wait_from_submit_p50_s"] <= \
        s["queue_wait_from_submit_p90_s"]
    assert 0 < s["ttft_from_submit_p50_s"] <= s["ttft_from_submit_p90_s"]
    assert s["queue_wait_from_submit_p90_s"] < s["ttft_from_submit_p90_s"]
    c = eng.counters
    assert s["host_reads_per_step"] == pytest.approx(
        c["serving.host_reads"] / c["serving.steps"])
    assert s["decode_occupancy"] == pytest.approx(
        c["serving.slot_steps"] / (c["serving.decode_steps"] * 2))
    assert 0 < s["decode_occupancy"] <= 1
    assert s["compiles"] > 0            # a new engine's steps compile
    assert s["tokens"] == sum(len(r.out_ids) for r in reqs)
    assert s["throughput_tok_s"] > 0
