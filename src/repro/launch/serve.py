"""Serving launcher: batched requests through the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch dcache-agent-150m \
        --requests 8 --max-new 24
    PYTHONPATH=src python -m repro.launch.serve --preset full

``--preset smoke`` serves the arch's reduced config (CPU-sized, vocab 512
for the byte tokenizer); ``--preset full`` serves the real config and vocab
(chip-sized). Weights are random, drawn from a fixed seed.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import ALL_IDS, get_config
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import Init, unbox
from repro.models.model import init_model
from repro.serving.engine import ServingEngine

PROMPTS = [
    "Plot the xview1 images from 2022 around Newport Beach",
    "Detect airplanes in this area",
    "Show fair1m and xview1 imagery from 2022",
    "Classify the land cover near Houston",
    "How many ships were detected in Miami in 2021?",
    "Render a heatmap of detections for Seattle",
    "What does the Denver area look like?",
    "Count the cloudy scenes in sentinel2-2020",
]


# engine sizes per preset; "full" holds a few-shot decision prompt whole
PRESETS = {
    "smoke": dict(max_batch=4, max_len=256),
    "full": dict(max_batch=8, max_len=4096),
}


def serve_config(arch: str, preset: str) -> ModelConfig:
    cfg = get_config(arch)
    if preset == "smoke":
        # the reduced vocab (257) is below the byte tokenizer's minimum
        cfg = dataclasses.replace(cfg.reduced(), vocab_size=512)
    return cfg


def build_engine(cfg: ModelConfig, *, max_batch: int, max_len: int,
                 seed: int = 0) -> ServingEngine:
    """A serving engine over ``cfg`` with weights drawn from ``seed``."""
    def init(key):  # one program: eager init compiles each op separately
        return unbox(init_model(Init(key, dtype=cfg.jnp_dtype), cfg))[0]

    params = jax.jit(init)(jax.random.PRNGKey(seed))
    return ServingEngine(cfg, params, max_batch=max_batch, max_len=max_len)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dcache-agent-150m", choices=ALL_IDS)
    ap.add_argument("--preset", default="smoke", choices=sorted(PRESETS))
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int,
                    help="decode slots (default: the preset's)")
    ap.add_argument("--max-len", type=int,
                    help="per-slot KV length (default: the preset's)")
    args = ap.parse_args()

    enable_compile_cache()
    sizes = PRESETS[args.preset]
    eng = build_engine(serve_config(args.arch, args.preset),
                       max_batch=args.max_batch or sizes["max_batch"],
                       max_len=args.max_len or sizes["max_len"])
    reqs = [eng.submit(PROMPTS[i % len(PROMPTS)], max_new_tokens=args.max_new)
            for i in range(args.requests)]
    eng.run_until_done()
    for r in reqs:
        print(f"[{r.rid}] {eng.tok.decode(r.prompt_ids)!r} -> "
              f"{eng.tok.decode(r.out_ids)!r}")
    print("stats:", eng.stats())


if __name__ == "__main__":
    main()
