"""End-to-end driver (the paper's kind is agent *serving*): train a small
LM briefly, then serve batched requests through the continuous-batching
engine — including using it as the ``JaxLLM`` cache-decision backend.

    PYTHONPATH=src python examples/serve_llm.py [--steps 120]
"""
import argparse
import dataclasses
import time

import jax

from repro.agent.backends import JaxLLM
from repro.configs import get_config
from repro.models import Init, init_model, unbox
from repro.serving import ServingEngine
from repro.training import AdamWConfig, TokenStream, TrainLoop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args()

    cfg = dataclasses.replace(get_config("dcache-agent-150m").reduced(),
                              vocab_size=512, n_layers=4, d_model=128,
                              d_ff=512, n_heads=4, n_kv_heads=2)
    print(f"model: {cfg.param_count()/1e6:.2f}M params")
    params, _ = unbox(init_model(Init(jax.random.PRNGKey(0),
                                      dtype=cfg.jnp_dtype), cfg))

    # -- short training run -------------------------------------------------
    stream = TokenStream(cfg, batch=8, seq=64, seed=0)
    loop = TrainLoop(cfg, AdamWConfig(lr=1e-3, warmup_steps=5,
                                      total_steps=args.steps),
                     params, iter(stream.next_batch, None), ckpt_every=0)
    t0 = time.time()
    loop.run(args.steps)
    print(f"trained {args.steps} steps in {time.time()-t0:.1f}s: "
          f"loss {loop.history[0]:.3f} -> {loop.history[-1]:.3f}")

    # -- batched serving ----------------------------------------------------
    eng = ServingEngine(cfg, loop.params, max_batch=4, max_len=192)
    prompts = [
        "Plot the xview1 images from 2022",
        "Detect airplanes around Newport Beach",
        "Show fair1m and xview1 imagery",
        "Classify land cover near Houston",
        "Count ships in Miami 2021",
        "Heatmap of detections for Seattle",
        "Describe the Denver area",
        "List cloudy sentinel2 scenes",
    ][: args.requests]
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    t0 = time.time()
    eng.run_until_done()
    s = eng.stats()
    print(f"\nserved {s['finished']} requests in {time.time()-t0:.1f}s "
          f"({s['throughput_tok_s']:.1f} tok/s, "
          f"ttft p50 {s['ttft_from_submit_p50_s']*1e3:.0f} ms, "
          f"{s['host_reads_per_step']:.1f} host reads/step)")
    for r in reqs[:3]:
        print(f"  [{r.rid}] -> {eng.tok.decode(r.out_ids)!r}")

    # -- the served model as the cache-decision LLM -------------------------
    llm = JaxLLM(eng, max_new_tokens=24)
    out = llm.complete("Cache: {}  Required keys: [\"xview1-2022\"]  "
                       "Answer (JSON): ")
    print(f"\nJaxLLM cache-decision completion (untuned byte-LM): {out!r}")
    print("(the SimLLM backend provides the calibrated decisions for the "
          "benchmarks; this shows the real serving path wired end-to-end)")


if __name__ == "__main__":
    main()
