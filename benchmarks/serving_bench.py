"""Serving-engine and kernel micro-benchmarks (real wall time on CPU).

us_per_call numbers are CPU-interpret figures — the TPU target is what the
dry-run/roofline reports; these catch regressions and prove the paths run.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import Init, init_model, unbox


def bench_serving(n_requests: int = 6, max_new: int = 8) -> List[str]:
    from repro.serving import ServingEngine
    cfg = dataclasses.replace(get_config("dcache-agent-150m").reduced(),
                              vocab_size=512)
    params, _ = unbox(init_model(Init(jax.random.PRNGKey(0),
                                      dtype=cfg.jnp_dtype), cfg))
    eng = ServingEngine(cfg, params, max_batch=4, max_len=128)
    for i in range(n_requests):
        eng.submit(f"benchmark request number {i}", max_new_tokens=max_new)
    t0 = time.perf_counter()
    eng.run_until_done()
    dt = time.perf_counter() - t0
    s = eng.stats()
    return [
        "bench,metric,value",
        f"serving,requests,{s['finished']}",
        f"serving,wall_s,{dt:.3f}",
        f"serving,throughput_tok_s,{s['throughput_tok_s']:.2f}",
        f"serving,ttft_p50_s,{s['ttft_from_submit_p50_s']:.3f}",
    ]


def bench_cache_ops(n: int = 5_000) -> List[str]:
    """Host-side cache op latency (the actual mechanism the paper adds)."""
    from repro.core.cache import DataCache
    from repro.core.policies import make_policy
    c = DataCache(capacity=5)
    pol = make_policy("lru")
    keys = [f"d{i}-20{i % 10:02d}" for i in range(40)]
    t0 = time.perf_counter()
    for i in range(n):
        k = keys[i % len(keys)]
        if k in c:
            c.get(k)
        else:
            victim = pol.victim(c.entries()) if len(c) >= 5 else None
            c.put(k, i, 1, victim=victim)
    us = (time.perf_counter() - t0) / n * 1e6
    return [f"cache_ops,us_per_call,{us:.2f}"]


def bench_kernels() -> List[str]:
    from repro.kernels import ops
    rows = []
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    out = ops.flash_attention(q, k, v, block_q=128, block_k=128)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(ops.flash_attention(q, k, v, block_q=128,
                                                  block_k=128))
    rows.append(f"kernel_flash_attn_interpret,us_per_call,"
                f"{(time.perf_counter()-t0)/3*1e6:.0f}")
    return rows
