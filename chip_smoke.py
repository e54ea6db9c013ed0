#!/usr/bin/env python3
"""Smoke run of the served decision path on one TPU chip.

    python chip_smoke.py [--seed N]

Serves dcache-agent-150m at its full width (12 layers, d_model 768, vocab
32768, bf16; random weights drawn from ``--seed``) through the program's own
entry points, in one process and in this order:

1. device      - a TPU must be attached; there is no CPU fallback;
2. kernels     - each Pallas kernel once, compiled, against its oracle in
                 ``repro.kernels.ref``;
3. serving     - ``ServingEngine`` serves few-shot cache-decision prompts
                 from ``repro.core.prompts``;
4. controller  - ``JaxLLM`` behind ``LLMController`` plans reads and updates
                 for GeoLLM tasks: prompt -> served model -> parse -> fallback;
5. consistency - decode logits over the ring-buffer KV cache against a
                 no-cache recompute of the same prefix.

Times printed on the way are set-up and sanity figures, not benchmark
results. Any failure raises before the last line, which is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time
from typing import Dict, List

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.agent.backends import JaxLLM  # noqa: E402
from repro.agent.geollm.datastore import GeoDataStore  # noqa: E402
from repro.agent.geollm.workload import WorkloadSampler  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.core import profiling, prompts  # noqa: E402
from repro.core.admission import TinyLFU  # noqa: E402
from repro.core.cache import DataCache  # noqa: E402
from repro.core.controller import LLMController  # noqa: E402
from repro.core.plan_cache import PlanCachePolicy  # noqa: E402
from repro.core.policies import make_policy  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.decode_attention import decode_attention  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.rmsnorm import rmsnorm  # noqa: E402
from repro.kernels.rwkv_wkv import wkv  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    PRESETS,
    build_engine,
    serve_config,
)
from repro.models.model import decode_step, prefill_step  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

ARCH = "dcache-agent-150m"
WKV_ARCH = "rwkv6-7b"       # the kernel's only user: its head width is 64
BF16_TOL = dict(atol=2e-2, rtol=2e-2)
F32_TOL = dict(atol=1e-3, rtol=1e-3)
# decode-vs-recompute logits: max |diff| over max |logit|, bf16 activations
LOGITS_RTOL = 5e-2
# the consistency request: with random weights attention is near uniform, so
# one wrong key among a thousand moves the logits less than bf16 noise does;
# over a short prefix every cached key carries visible weight
CONSISTENCY_PROMPT_BYTES = 32


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class SmokeFailure(AssertionError):
    """A phase produced a wrong result."""


def require(ok, msg) -> None:
    """``assert`` that ``python -O`` cannot strip."""
    if not ok:
        raise SmokeFailure(msg)


# -- 1. device ---------------------------------------------------------------

def require_tpu(devices) -> Dict[str, object]:
    """The device record of the final line; raises unless a TPU is attached."""
    d = devices[0]
    if d.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found {d.platform!r} "
            f"({d.device_kind}). There is no CPU fallback.")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# -- 2. kernels --------------------------------------------------------------

def _assert_close(name: str, got, want, tol: Dict[str, float]) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    require(got.shape == want.shape, (name, got.shape, want.shape))
    require(np.isfinite(got).all(), f"{name}: non-finite output")
    np.testing.assert_allclose(got, want, err_msg=name, **tol)
    return float(np.max(np.abs(got - want)))


def check_kernels(cfg: ModelConfig, wkv_cfg: ModelConfig, *, batch: int,
                  cache_len: int, seq: int, seed: int,
                  interpret: bool = False) -> Dict[str, float]:
    """Each Pallas kernel once at ``cfg``'s widths against its oracle.

    The oracles run at the highest matmul precision, so the TPU's default
    bf16 passes for f32 dots do not blur the reference."""
    rng = np.random.default_rng(seed)

    def arr(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.normal(size=shape), dtype)

    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    err = {}
    with jax.default_matmul_precision("highest"):
        q, k, v = arr(batch, hq, d), arr(batch, hkv, cache_len, d), \
            arr(batch, hkv, cache_len, d)
        pos = jnp.asarray(rng.integers(1, 2 * cache_len, batch), jnp.int32)
        err["decode_attention"] = _assert_close(
            "decode_attention",
            decode_attention(q, k, v, pos, interpret=interpret),
            ref.ref_decode_attention(q, k, v, pos), BF16_TOL)

        q, k, v = arr(1, hq, seq, d), arr(1, hkv, seq, d), arr(1, hkv, seq, d)
        err["flash_attention"] = _assert_close(
            "flash_attention", flash_attention(q, k, v, interpret=interpret),
            ref.ref_flash_attention(q, k, v), BF16_TOL)

        x, g = arr(seq, cfg.d_model), arr(cfg.d_model)
        err["rmsnorm"] = _assert_close(
            "rmsnorm", rmsnorm(x, g, interpret=interpret),
            ref.ref_rmsnorm(x, g), BF16_TOL)

        h, hd = wkv_cfg.n_ssm_heads, wkv_cfg.ssm.head_dim
        r, kk, vv = (arr(1, h, seq // 8, hd, dtype=jnp.float32)
                     for _ in range(3))
        w = jnp.asarray(rng.uniform(0.8, 0.999, (1, h, seq // 8, hd)),
                        jnp.float32)
        u = arr(h, hd, dtype=jnp.float32)
        y, s = wkv(r, kk, vv, w, u, interpret=interpret)
        y_ref, s_ref = ref.ref_wkv(r, kk, vv, w, u)
        err["wkv"] = max(_assert_close("wkv.y", y, y_ref, F32_TOL),
                         _assert_close("wkv.state", s, s_ref, F32_TOL))
    return err


# -- 3. serving --------------------------------------------------------------

def decision_prompts(seed: int) -> List[str]:
    """Few-shot cache-decision prompts of four kinds, two of each: read and
    update (paper Fig. 2), admission and plan-cache admission."""
    tasks = WorkloadSampler(reuse_rate=0.8, seed=seed).sample(2)
    cache = DataCache(capacity=3)
    for key in tasks[0].required_keys[:2]:
        cache.put(key, None, 70_000_000)
    held = cache.contents_json()
    lru = make_policy("lru").describe()
    out = []
    for i, t in enumerate(tasks):
        out.append(prompts.read_decision_prompt(
            t.query, t.required_keys, held, few_shot=True))
        out.append(prompts.update_decision_prompt(
            lru, t.required_keys, held, cache.capacity, few_shot=True))
        out.append(prompts.admission_decision_prompt(
            TinyLFU().describe(), t.required_keys[0], cache.keys()[0],
            1 + i, 4 - i, held, few_shot=True))
        out.append(prompts.plan_cache_decision_prompt(
            PlanCachePolicy().describe(), f"detect>plot#{i}",
            f"count>vqa#{i}", 5 - i, 1 + i, 180.0, few_shot=True))
    return out


def _check_finished(eng: ServingEngine, reqs) -> None:
    for r in reqs:
        require(r.done, f"request {r.rid} did not finish")
        require(r.out_ids, f"request {r.rid} sampled nothing")
        bad = [t for t in r.out_ids if not 0 <= t < eng.cfg.vocab_size]
        require(not bad, f"request {r.rid} sampled ids outside the vocab: "
                         f"{bad}")


def check_serving(eng: ServingEngine, texts: List[str], *,
                  max_new_tokens: int, window: int) -> Dict[str, float]:
    """Serve every prompt to completion, then time a window of full-batch
    decode steps that ends in ``block_until_ready``."""
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=max_new_tokens) for p in texts]
    eng.run_until_done()
    first_pass_s = time.perf_counter() - t0
    _check_finished(eng, reqs)

    reqs = [eng.submit(p, max_new_tokens=window + 2) for p in texts]
    eng.step()                      # admits (prefills) every waiting slot
    t0 = time.perf_counter()
    for _ in range(window):
        eng.step()
    jax.block_until_ready(eng.cache)
    step_s = (time.perf_counter() - t0) / window
    eng.run_until_done()
    _check_finished(eng, reqs)
    return {"requests": 2 * len(texts), "first_pass_s": first_pass_s,
            "decode_step_ms": 1e3 * step_s,
            "prompt_bytes_max": max(len(p.encode()) for p in texts)}


# -- 4. controller -----------------------------------------------------------

def check_controller(eng: ServingEngine, *, n_tasks: int,
                     seed: int) -> Dict[str, int]:
    """Cache-op decisions for GeoLLM tasks, made by the served model."""
    cache = DataCache(capacity=3)
    ctrl = LLMController(cache, make_policy("lru"), llm=JaxLLM(eng))
    store = GeoDataStore(clock=None)
    served = len(eng.finished)
    for t in WorkloadSampler(reuse_rate=0.8, seed=seed + 1).sample(n_tasks):
        plan = ctrl.plan_reads(t.query, t.required_keys)
        require(set(plan.choices) == set(t.required_keys), plan.choices)
        require(set(plan.choices.values()) <= {"read_cache", "load_db"},
                plan.choices)
        ctrl.update(plan.load_keys(), store.peek, lambda f: f.size_bytes)
        require(len(cache) <= cache.capacity, cache.keys())
    calls = len(eng.finished) - served
    require(calls >= n_tasks, f"only {calls} completions for {n_tasks} tasks")
    return {"llm_calls": calls, "parse_fallbacks": ctrl.parse_fallbacks,
            "graded_decisions": cache.stats.llm_total_decisions,
            "degraded": ctrl.degraded}


# -- 5. consistency ----------------------------------------------------------

def check_cache_consistency(eng: ServingEngine, prompt: str, *,
                            steps: int) -> Dict[str, float]:
    """Greedy-decode ``steps`` tokens over the ring-buffer KV cache and hold
    each step's logits to a prefill of the whole prefix, with no cache."""
    cfg, params = eng.cfg, eng.params
    ids = eng.tok.encode(prompt)[-(eng.max_len // 2):]
    bucket = 1 << (len(ids) + steps - 1).bit_length()
    prefill = jax.jit(functools.partial(prefill_step, cfg,
                                        max_len=eng.max_len))
    decode = jax.jit(functools.partial(decode_step, cfg))

    def recompute(seq):
        toks = jnp.asarray([seq + [0] * (bucket - len(seq))], jnp.int32)
        return prefill(params, {"tokens": toks},
                       true_lens=jnp.asarray([len(seq)], jnp.int32))

    cache, logits = recompute(ids)
    worst, matches = 0.0, 0
    for _ in range(steps):
        tok = int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))
        ids = ids + [tok]
        logits, cache = decode(params, jnp.asarray([[tok]], jnp.int32), cache)
        got = np.asarray(logits[0, -1, :cfg.vocab_size], np.float32)
        want = np.asarray(recompute(ids)[1][0, -1, :cfg.vocab_size],
                          np.float32)
        require(np.isfinite(got).all(), "non-finite decode logits")
        rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        worst = max(worst, rel)
        matches += int(np.argmax(got) == np.argmax(want))
    require(worst <= LOGITS_RTOL, f"decode logits drift from the recompute: "
                                  f"{worst:.4f} > {LOGITS_RTOL}")
    require(2 * matches > steps, f"argmax agrees on {matches}/{steps} steps")
    return {"steps": steps, "max_rel_err": worst, "argmax_matches": matches}


# -- main --------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args(argv)

    device = require_tpu(jax.devices())
    log("device", f"platform={device['platform']} kind={device['kind']} "
                  f"count={device['count']}")
    log("device", f"compile cache: {enable_compile_cache()}")
    profiling.count_compiles()

    cfg = serve_config(ARCH, "full")
    sizes = PRESETS["full"]
    t0 = time.perf_counter()
    err = check_kernels(cfg, get_config(WKV_ARCH), batch=sizes["max_batch"],
                        cache_len=sizes["max_len"], seq=2048, seed=args.seed)
    log("kernels", f"ok in {time.perf_counter() - t0:.2f} s, max |err| "
                   + " ".join(f"{k}={v:.3g}" for k, v in err.items()))

    t0 = time.perf_counter()
    eng = build_engine(cfg, max_batch=sizes["max_batch"],
                       max_len=sizes["max_len"], seed=args.seed)
    jax.block_until_ready(eng.params)
    log("serving", f"{cfg.name}: {cfg.n_layers} layers, d_model "
                   f"{cfg.d_model}, vocab {cfg.vocab_size}, {cfg.dtype}, "
                   f"{cfg.param_count() / 1e6:.1f}M params; engine "
                   f"max_batch={eng.max_batch} max_len={eng.max_len}; "
                   f"init {time.perf_counter() - t0:.2f} s")
    texts = decision_prompts(args.seed)
    c0 = profiling.snapshot()
    st = check_serving(eng, texts, max_new_tokens=32, window=16)
    c = profiling.delta(c0, profiling.snapshot())
    log("serving", f"ok: {st['requests']} requests finished, longest "
                   f"prompt {st['prompt_bytes_max']} bytes; first pass "
                   f"{st['first_pass_s']:.2f} s with "
                   f"{c.get('jax.compiles', 0):.0f} compiles taking "
                   f"{c.get('jax.compile_s', 0.0):.2f} s; "
                   f"decode step {st['decode_step_ms']:.2f} ms at batch "
                   f"{eng.max_batch} (sanity figures, not metrics)")

    ct = check_controller(eng, n_tasks=3, seed=args.seed)
    log("controller", f"ok: {ct['llm_calls']} served completions, "
                      f"parse_fallbacks={ct['parse_fallbacks']} "
                      f"graded_decisions={ct['graded_decisions']} "
                      f"degraded={ct['degraded']}")

    cs = check_cache_consistency(eng, texts[0][-CONSISTENCY_PROMPT_BYTES:],
                                 steps=6)
    log("consistency", f"ok: {cs['steps']} decode steps, max relative "
                       f"logit error {cs['max_rel_err']:.3g}, argmax "
                       f"agrees on {cs['argmax_matches']}/{cs['steps']}")

    mem = jax.devices()[0].memory_stats() or {}
    c = profiling.snapshot()
    log("device", f"{c.get('jax.compiles', 0):.0f} compiles, "
                  f"{c.get('jax.compile_s', 0.0):.2f} s in the "
                  f"backend compiler; peak device memory "
                  f"{mem.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
