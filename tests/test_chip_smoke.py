"""chip_smoke.py's phases at the reduced config, on the CPU.

The phases run here with the kernels in interpret mode; ``main`` itself
must refuse to run anywhere but on a TPU.
"""
import importlib.util
import pathlib

import jax
import pytest

from repro.configs import get_config
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


@pytest.fixture(scope="module")
def engine():
    cfg = cs.serve_config(cs.ARCH, "smoke")
    return cs.build_engine(cfg, **cs.PRESETS["smoke"], seed=0)


def test_device_guard_refuses_cpu():
    with pytest.raises(RuntimeError, match="needs a TPU"):
        cs.require_tpu(jax.devices())


def test_main_stops_at_device_phase(capsys):
    with pytest.raises(RuntimeError, match="no CPU fallback"):
        cs.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_kernel_phase_matches_oracles():
    cfg = cs.serve_config(cs.ARCH, "smoke")
    err = cs.check_kernels(cfg, get_config(cs.WKV_ARCH).reduced(), batch=2,
                           cache_len=128, seq=128, seed=0, interpret=True)
    assert set(err) == {"decode_attention", "flash_attention", "rmsnorm",
                        "wkv"}


def test_serving_phase_finishes_decision_prompts(engine):
    texts = cs.decision_prompts(0)
    assert len(texts) >= 8
    for marker in ("Respond with a JSON object mapping each key",
                   "return the NEW cache state", "ADMIT the candidate",
                   "PLAN-CACHE controller"):
        assert any(marker in t for t in texts), marker
    st = cs.check_serving(engine, texts, max_new_tokens=6, window=3)
    assert st["requests"] == 2 * len(texts)
    assert not engine.waiting and all(s is None for s in engine.slots)


def test_controller_phase_runs_the_served_model(engine):
    ct = cs.check_controller(engine, n_tasks=2, seed=0)
    assert ct["llm_calls"] >= 2
    assert ct["parse_fallbacks"] + ct["graded_decisions"] >= 1


def test_consistency_phase_agrees_with_recompute(engine):
    prompt = cs.decision_prompts(0)[0][-cs.CONSISTENCY_PROMPT_BYTES:]
    res = cs.check_cache_consistency(engine, prompt, steps=4)
    assert res["max_rel_err"] <= cs.LOGITS_RTOL
    assert res["argmax_matches"] >= 3


def test_compile_cache_dir_follows_env(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    # with the variable set, JAX already reads it: nothing is overridden
    assert enable_compile_cache({"JAX_COMPILATION_CACHE_DIR": "/c"}) == \
        pathlib.Path("/c")
    assert calls == []
    assert enable_compile_cache({}) == REPO_CACHE_DIR == ROOT / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", str(REPO_CACHE_DIR))]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
