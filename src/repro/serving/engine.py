"""Continuous-batching serving engine.

Slot-based scheduler over the unified model's (prefill_step, decode_step):
a fixed decode batch of ``max_batch`` slots steps in lockstep (one jitted
decode per engine step); requests are admitted into free slots by running a
single-row prefill (prompt bucketed to a power of two to bound recompiles —
right-padding is masked by construction, see ``prefill_step``) and
scattering the row into the batch cache. Completed rows free their slot.
The decode step donates the batch cache: each step writes its new K/V rows
into the ring in place, and the device holds one cache.

This is the vLLM-style core scaled down: the KV "pages" are per-slot ring
buffers; at production scale the same engine runs under pjit with the cache
sharded (batch -> data, kv -> model) — exactly what the decode dry-run
shapes lower.

While the JAX profiler traces, ``step`` marks its phases as ``serving.*``
spans (``core/profiling.span``), nested on the calling thread: ``step``
holds one ``admit`` per admitted request (``prefill``, ``install``,
``first_token``), then ``decode``, ``sample`` and ``retire``. Every step adds
its ``serving.*`` counts (admissions, admissions whose prefill runs the flash
kernel, prefill tokens true and padded, blocking device-to-host reads, tokens
out, decode batch slots) to ``profiling.COUNTERS`` in one call.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.shapes import cache_specs, effective_cache_len
from repro.core import profiling
from repro.models.attention import flash_prefill
from repro.models.model import decode_step, prefill_step
from repro.serving.sampler import sample
from repro.serving.tokenizer import MIN_VOCAB, ByteTokenizer


@dataclasses.dataclass
class Request:
    rid: int
    prompt_ids: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    out_ids: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None


def _named_jit(fn, *args, **kw):
    """``jax.jit`` of ``fn`` with some arguments bound, tracing under
    ``fn``'s own name (``jit_decode_step``; a bare partial traces as
    ``jit__unknown``)."""
    bound = functools.partial(fn, *args, **kw)
    bound.__name__ = fn.__name__
    return jax.jit(bound)


def _bucket(n: int, cap: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 max_len: int = 512, tokenizer: Optional[ByteTokenizer] = None):
        assert cfg.vocab_size >= MIN_VOCAB, "byte tokenizer needs vocab>=258"
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.tok = tokenizer or ByteTokenizer()
        self.cache = self._empty_cache()
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.waiting: List[Request] = []
        self.finished: List[Request] = []
        self._rid = 0
        self._rng = jax.random.PRNGKey(0)
        # the decode program, (params, tokens, cache) -> (logits, cache');
        # ``step`` runs whatever it holds (a test may wrap it) under a jit
        # that donates the cache
        self._decode = _named_jit(decode_step, cfg)
        self._donating = (None, None)
        self._prefill = {}
        # this engine's totals of the ``serving.*`` counters it adds to
        # ``profiling.COUNTERS`` once per step
        self.counters: Dict[str, int] = collections.Counter()
        profiling.count_compiles()
        self._compiles_at_build = profiling.snapshot().get("jax.compiles", 0)

    # -- cache plumbing -------------------------------------------------------
    def _empty_cache(self):
        specs = cache_specs(self.cfg, self.max_batch, self.max_len)
        return {k: jnp.zeros(v.shape, v.dtype) for k, v in specs.items()}

    def _decode_donated(self):
        """``_decode`` under a jit that donates the cache: the new cache
        takes the old one's buffers, so a step writes only its new K/V rows
        into the ring and the device holds one cache."""
        if self._donating[0] is not self._decode:
            self._donating = (self._decode,
                              jax.jit(self._decode, donate_argnums=2))
        return self._donating[1]

    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill:
            self._prefill[bucket] = _named_jit(prefill_step, self.cfg,
                                               max_len=self.max_len)
        return self._prefill[bucket]

    def _install(self, slot: int, row_cache: Dict):
        """Scatter a B=1 prefill cache into slot b of the batch cache."""
        C = effective_cache_len(self.cfg, self.max_len)
        for k, v in row_cache.items():
            cur = self.cache[k]
            if k == "pos":
                self.cache[k] = cur.at[slot].set(v[0])
            elif cur.ndim >= 3 and cur.shape[1] == self.max_batch:
                # (L, B, ...) layer-stacked
                row = v[:, 0]
                if k in ("k", "v"):
                    rc = row.shape[1]
                    if rc < C:
                        pad = jnp.zeros((row.shape[0], C - rc, row.shape[2]),
                                        row.dtype)
                        row = jnp.concatenate([row, pad], axis=1)
                    else:
                        row = row[:, :C]
                self.cache[k] = cur.at[:, slot].set(row)
            else:
                self.cache[k] = cur.at[slot].set(v[0])

    # -- public API -----------------------------------------------------------
    def submit(self, prompt: str, max_new_tokens: int = 32,
               temperature: float = 0.0) -> Request:
        ids = self.tok.encode(prompt)[- (self.max_len // 2):]
        req = Request(rid=self._rid, prompt_ids=ids,
                      max_new_tokens=max_new_tokens, temperature=temperature,
                      submitted_at=time.perf_counter())
        self._rid += 1
        self.waiting.append(req)
        return req

    def _admit(self, counts: Dict[str, int]):
        exact = self.cfg.family in ("ssm", "hybrid")  # recurrent state: no pad
        for slot in range(self.max_batch):
            if self.slots[slot] is not None or not self.waiting:
                continue
            req = self.waiting.pop(0)
            req.admitted_at = time.perf_counter()
            n = len(req.prompt_ids)
            bucket = n if exact else _bucket(n, self.max_len)
            with profiling.span("serving.admit", rid=req.rid, slot=slot,
                                prompt_len=n, bucket=bucket):
                ids = req.prompt_ids + [0] * (bucket - n)
                batch = {"tokens": jnp.asarray([ids], jnp.int32)}
                with profiling.span("serving.prefill"):
                    row_cache, logits = self._prefill_fn(bucket)(
                        self.params, batch,
                        true_lens=jnp.asarray([n], jnp.int32))
                with profiling.span("serving.install"):
                    self._install(slot, row_cache)
                with profiling.span("serving.first_token"):
                    self._rng, k = jax.random.split(self._rng)
                    tok = sample(logits[:, -1].astype(jnp.float32), k,
                                 temperature=req.temperature)
                    req.out_ids.append(int(tok[0]))
            req.first_token_at = time.perf_counter()
            self.slots[slot] = req
            counts["serving.admissions"] += 1
            counts["serving.prefill_kernel"] += flash_prefill(
                self.cfg, bucket, return_kv=True)
            counts["serving.prefill_tokens"] += n
            counts["serving.prefill_padded_tokens"] += bucket
            counts["serving.host_reads"] += 1
            counts["serving.tokens_out"] += 1

    def step(self) -> int:
        """One engine step: admit waiting requests, decode all active slots."""
        counts = collections.Counter({"serving.steps": 1})
        with profiling.span("serving.step"):
            self._admit(counts)
            active = [i for i, r in enumerate(self.slots) if r is not None]
            if active:
                self._decode_active(active, counts)
        profiling.add_many(counts)
        self.counters.update(counts)
        return len(active)

    def _decode_active(self, active: List[int], counts: Dict[str, int]):
        tokens = np.zeros((self.max_batch, 1), np.int32)
        for i in active:
            tokens[i, 0] = self.slots[i].out_ids[-1]
        with profiling.span("serving.decode"):
            logits, self.cache = self._decode_donated()(
                self.params, jnp.asarray(tokens), self.cache)
        with profiling.span("serving.sample"):
            self._rng, k = jax.random.split(self._rng)
            nxt = np.asarray(sample(logits[:, -1].astype(jnp.float32), k))
        with profiling.span("serving.retire"):
            for i in active:
                req = self.slots[i]
                tok = int(nxt[i])
                req.out_ids.append(tok)
                limit_hit = len(req.out_ids) >= req.max_new_tokens
                pos_cap = int(self.cache["pos"][i]) >= self.max_len - 1
                if tok == self.tok.eos_id or limit_hit or pos_cap:
                    req.done = True
                    req.finished_at = time.perf_counter()
                    self.finished.append(req)
                    self.slots[i] = None
        n = len(active)
        counts["serving.decode_steps"] += 1
        counts["serving.slot_steps"] += n
        counts["serving.host_reads"] += 1 + n   # the sampled ids, each pos
        counts["serving.tokens_out"] += n

    def run_until_done(self, max_steps: int = 10_000):
        while (self.waiting or any(s is not None for s in self.slots)) \
                and max_steps > 0:
            self.step()
            max_steps -= 1

    def generate_text(self, prompt: str, max_new_tokens: int = 32,
                      temperature: float = 0.0) -> str:
        req = self.submit(prompt, max_new_tokens, temperature)
        self.run_until_done()
        return self.tok.decode(req.out_ids)

    # -- metrics ---------------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Finished requests' queue wait and time to first token, both timed
        from ``submit`` (p50, p90); host reads per step, mean share of the
        decode batch's slots in use and backend compiles in the process
        since the engine was built, from the counters; tokens served and
        their rate from the first submit to the last finish."""
        done = self.finished
        if not done:
            return {"finished": 0}
        wait = [r.admitted_at - r.submitted_at for r in done]
        ttft = [r.first_token_at - r.submitted_at for r in done]
        c = self.counters
        toks = c["serving.tokens_out"]
        wall = max(r.finished_at for r in done) - min(
            r.submitted_at for r in done)
        compiles = profiling.snapshot().get("jax.compiles", 0)
        return {"finished": len(done),
                "queue_wait_from_submit_p50_s": float(np.percentile(wait, 50)),
                "queue_wait_from_submit_p90_s": float(np.percentile(wait, 90)),
                "ttft_from_submit_p50_s": float(np.percentile(ttft, 50)),
                "ttft_from_submit_p90_s": float(np.percentile(ttft, 90)),
                "host_reads_per_step":
                    c["serving.host_reads"] / max(c["serving.steps"], 1),
                "decode_occupancy": c["serving.slot_steps"]
                    / max(c["serving.decode_steps"] * self.max_batch, 1),
                "compiles": int(compiles - self._compiles_at_build),
                "tokens": toks,
                "throughput_tok_s": toks / wall if wall > 0 else 0.0}
