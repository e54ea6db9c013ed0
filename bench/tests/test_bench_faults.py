"""Whole runs at a tiny size on the CPU with the timed path broken
underneath: each fault a served cell can have makes ``correct`` false."""
import pytest

from bench import harness
from bench.tests.helpers import run_tiny


def _alter_tokens(eng):
    import repro.serving.engine as engine_mod
    orig = engine_mod.sample

    def shifted(logits, rng, **kw):
        return (orig(logits, rng, **kw) + 1) % eng.cfg.vocab_size
    engine_mod.sample = shifted
    return lambda: setattr(engine_mod, "sample", orig)


def _freeze_state(eng):
    orig = eng._decode

    def frozen(params, tokens, cache):
        logits, _ = orig(params, tokens, cache)
        return logits, cache
    eng._decode = frozen
    return lambda: None


@pytest.mark.parametrize("fault", [_alter_tokens, _freeze_state],
                         ids=["token-altered", "state-unchanged"])
@pytest.mark.parametrize("cell", ["granite-3-2b.decisions",
                                  "granite-3-2b.cot-backlog"])
def test_fault_is_not_correct(cell, fault):
    undo = []
    try:
        run, checks, _ = run_tiny(cell, seconds=1.5,
                                  eng_hook=lambda e: undo.append(fault(e)))
    finally:
        for u in undo:
            u()
    assert not harness.is_correct(checks), checks
    assert checks["max_logit_gap"]["value"] > \
        checks["max_logit_gap"]["limit"]


def test_no_finished_request_is_not_correct(monkeypatch):
    monkeypatch.setattr(harness, "DRAIN_SECONDS", 0.5)
    run, checks, _ = run_tiny("granite-3-2b.decisions", seconds=1.5,
                              eng_hook=lambda e: setattr(
                                  e, "step", lambda: 0))
    assert checks["unfinished_requests"]["value"] == len(run.requests) > 0
    assert checks["max_logit_gap"]["value"] == harness.NO_ANSWER
    assert not harness.is_correct(checks)
