"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --preset smoke
    PYTHONPATH=src python -m repro.launch.train --arch dcache-agent-150m \
        --preset full --steps 300 --batch 8 --seq 256

``--preset smoke`` trains the arch's reduced config on CPU; ``--preset
full`` uses the real config (TPU-scale — on this container only sensible
for dcache-agent-150m). Checkpoints, fault-tolerance hooks, and the
prefetching data pipeline are all active in both presets.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from repro.configs import ALL_IDS, get_config
from repro.distributed.checkpoint import Checkpointer
from repro.distributed.fault_tolerance import HeartbeatMonitor
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import Init, unbox
from repro.models.model import init_model
from repro.training.data import Prefetcher, TokenStream
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import TrainLoop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dcache-agent-150m", choices=ALL_IDS)
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.preset == "smoke":
        cfg = cfg.reduced()
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"devices={jax.device_count()}")

    ini = Init(jax.random.PRNGKey(0), dtype=cfg.jnp_dtype)
    params, _ = unbox(init_model(ini, cfg))

    stream = TokenStream(cfg, batch=args.batch, seq=args.seq, seed=0)
    data = Prefetcher(stream, depth=2)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps)
    mon = HeartbeatMonitor()
    ck = Checkpointer(args.ckpt_dir, keep=2)
    loop = TrainLoop(cfg, opt_cfg, params, data, checkpointer=ck,
                     ckpt_every=args.ckpt_every, accum_steps=args.accum,
                     monitor=mon)
    if args.resume and loop.restore_if_available():
        print(f"resumed from step {loop.step_idx}")

    t0 = time.time()
    metrics = loop.run(args.steps)
    dt = time.time() - t0
    tok_s = args.steps * args.batch * args.seq / dt
    print(f"done: {metrics}  ({dt:.1f}s, {tok_s:.0f} tok/s, "
          f"loss {loop.history[0]:.3f} -> {loop.history[-1]:.3f}, "
          f"stragglers={len(mon.stragglers)})")
    data.close()


if __name__ == "__main__":
    main()
