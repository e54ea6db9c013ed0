#!/usr/bin/env python3
"""Write the decision-prompt pool that the traffic mixes read.

    PYTHONPATH=src python bench/tools/make_prompt_pool.py

Builds few-shot cache-decision prompts of the seven kinds the dCache sends
(read, update, admission, replication, recovery, coherence, plan-cache)
with the program's own builders in ``repro.core.prompts``, their arguments
drawn from ``WorkloadSampler`` and a fixed seed, and records beside each
prompt the byte length of the programmatic twin's (``SimLLM``) reply: the
length of one real decision. The pool is committed as data, so the
benchmark's traffic does not move when the program's prompts change.
"""
from __future__ import annotations

import json
import pathlib
import random

from repro.agent.backends import Profile, SimLLM
from repro.agent.geollm.workload import WorkloadSampler
from repro.core import prompts
from repro.core.admission import TinyLFU
from repro.core.cache import DataCache
from repro.core.plan_cache import PlanCachePolicy
from repro.core.policies import make_policy

POOL_SEED = 20240610
PER_KIND = 16
OUT = pathlib.Path(__file__).resolve().parents[1] / "mixes" / "decision_prompts.jsonl"

REPLICATION_POLICY = ("threshold (replicate when frequency >= 8; drop a "
                      "replica when frequency < 4).")
RECOVERY_POLICY = ("threshold (re-warm NOW when the key's estimated "
                   "frequency is >= 4; otherwise refill lazily on the next "
                   "demand access).")
COHERENCE_POLICY = ("serve a stale cached copy while its staleness is at "
                    "most 20 seconds; refresh now once the staleness "
                    "exceeds 20 seconds.")


def _cache_json(rng: random.Random, keys, capacity: int) -> DataCache:
    cache = DataCache(capacity=capacity)
    for key in rng.sample(keys, min(capacity, len(keys))):
        cache.put(key, None, rng.randrange(20_000_000, 90_000_000))
    return cache


def build_pool(seed: int = POOL_SEED, per_kind: int = PER_KIND):
    rng = random.Random(seed)
    tasks = WorkloadSampler(reuse_rate=0.8, seed=seed).sample(per_kind * 2)
    universe = sorted({k for t in tasks for k in t.required_keys})
    policies = ["lru", "lfu", "fifo"]
    out = []
    for i in range(per_kind):
        t = tasks[i]
        cache = _cache_json(rng, universe, rng.choice([3, 4, 5]))
        held = cache.contents_json()
        top = json.dumps({k: rng.randint(1, 12)
                          for k in rng.sample(universe, 4)})
        key, victim = rng.sample(universe, 2)
        freq = rng.randint(1, 12)
        out.append(("read", prompts.read_decision_prompt(
            t.query, t.required_keys, held, few_shot=True)))
        out.append(("update", prompts.update_decision_prompt(
            make_policy(policies[i % 3]).describe(), t.required_keys, held,
            cache.capacity, few_shot=True)))
        out.append(("admission", prompts.admission_decision_prompt(
            TinyLFU().describe(), key, victim, freq, rng.randint(1, 12),
            held, few_shot=True)))
        out.append(("replication", prompts.replication_decision_prompt(
            REPLICATION_POLICY, key, freq, rng.random() < 0.5, 8, 4, top,
            few_shot=True)))
        out.append(("recovery", prompts.recovery_decision_prompt(
            RECOVERY_POLICY, key, freq, 4, top, few_shot=True)))
        out.append(("coherence", prompts.coherence_decision_prompt(
            COHERENCE_POLICY, key, rng.uniform(0.5, 40.0), 20.0, freq,
            few_shot=True)))
        out.append(("plan_cache", prompts.plan_cache_decision_prompt(
            PlanCachePolicy().describe(), f"detect>plot#{i}",
            f"count>vqa#{i + 1}", freq, rng.randint(1, 12), 180.0,
            few_shot=True)))
    twin = SimLLM(Profile("gpt-4-turbo", "react", True), seed=seed)
    rows = []
    for kind, text in out:
        reply = twin.complete(text)
        rows.append({"kind": kind, "prompt": text,
                     "reply_bytes": len(reply.encode())})
    return rows


def main() -> None:
    rows = build_pool()
    with OUT.open("w") as f:
        for r in rows:
            f.write(json.dumps(r, sort_keys=True) + "\n")
    lens = [len(r["prompt"].encode()) for r in rows]
    reps = [r["reply_bytes"] for r in rows]
    print(f"{len(rows)} prompts, {min(lens)}-{max(lens)} bytes, replies "
          f"{min(reps)}-{max(reps)} bytes -> {OUT}")


if __name__ == "__main__":
    main()
