#!/usr/bin/env python3
"""Measurements that set the benchmark's fixed numbers; not run by checks.

    python bench/calibrate.py sweep  --workload <cell> --seeds <n> <n> ... \
        --seconds <s> --rates 0.5 1.0 ...
    python bench/calibrate.py limits --workload <cell> --seconds <s> \
        --seeds <n> <n> ...
    python bench/calibrate.py probe  --workload <cell> --seed <n> \
        --seconds <s> --out <file.json.gz>

``sweep`` drives one engine through the cell's open-loop mix at each rate
and prints the tails, for finding the knee (the highest rate at which the
p90 time to first token stays within ``--slo``). ``limits`` runs the cell
on each seed in one process and prints, per seed, the widest gap of a
served token below the float32 reference's best logit, beside the same gap
for the tokens a float8 control of the reference puts first: the two
readings the limit is set between. ``probe`` makes one traced run and keeps
the extracted device and host events, for reading a trace by hand and for
the recorded trace the tests hold. Run each on the chip, from the root of a
checkout.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import devtrace, harness, traffic  # noqa: E402
from bench.stats import percentile  # noqa: E402


def find_knee(points, slo_s):
    """Largest swept rate whose tail meets the SLO (``repro.core.traffic``)."""
    ok = [r for r, p in points if p <= slo_s]
    return max(ok) if ok else None


def _setup(cell_name):
    cell = harness.find_cell(cell_name)
    device = harness.require_chips(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cell, device, harness.peaks_for(device["kind"])


def sweep(args):
    """Each rate on each seed in turn through one engine; the tails of a
    rate are taken over the requests of all its seeds together."""
    from repro.launch.serve import build_engine
    cell, device, _ = _setup(args.workload)
    cfg = harness.program_config(cell.config, cell.reference)
    sizes = cell.config["engine"]
    eng = build_engine(cfg, max_batch=sizes["max_batch"],
                       max_len=sizes["max_len"],
                       seed=harness.engine_seed(args.seeds[0]))
    harness.warm_up(eng, traffic.load_pool(cell.mix))
    points = []
    for rate in args.rates:
        mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"],
                                           rate_per_s=rate))
        ttft, lat, wait1, wait2, per_seed = [], [], [], [], {}
        n = unfinished = steps_in = 0
        for seed in args.seeds:
            tr = traffic.Traffic(mix, args.seconds, seed)
            reqs, _, steps, t_open, _ = harness.drive(eng, tr, args.seconds)
            t1 = [r.token_t[0] - r.due for r in reqs if r.token_t]
            per_seed[seed] = percentile(t1, 90)
            ttft += t1
            lat += [r.handle.finished_at - r.due for r in reqs if r.done]
            half = t_open + args.seconds / 2
            wait1 += [r.admit_t - r.due for r in reqs
                      if r.admit_t and r.due < half]
            wait2 += [r.admit_t - r.due for r in reqs
                      if r.admit_t and r.due >= half]
            n += len(reqs)
            unfinished += sum(1 for r in reqs if not r.done)
            steps_in += sum(1 for s in steps if s.in_window)
        row = {"rate_per_s": rate, "requests": n, "unfinished": unfinished,
               "ttft_p50_s": percentile(ttft, 50),
               "ttft_p90_s": percentile(ttft, 90),
               "ttft_p90_s_per_seed": per_seed,
               "latency_p50_s": percentile(lat, 50),
               "latency_p90_s": percentile(lat, 90),
               "queue_wait_p90_first_half_s": percentile(wait1, 90),
               "queue_wait_p90_second_half_s": percentile(wait2, 90),
               "steps": steps_in}
        points.append((rate, row["ttft_p90_s"]))
        print(json.dumps(row), flush=True)
    knee = find_knee(points, args.slo)
    print(json.dumps({"knee_per_s": knee, "slo_ttft_p90_s": args.slo,
                      "rate_0.8_knee": None if knee is None else 0.8 * knee,
                      "device": device}), flush=True)


def limits(args):
    cell, device, peaks = _setup(args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        run, checks, ctl = harness.run_cell(cell, seed, args.seconds, False,
                                            t_start=t, peaks=peaks,
                                            control="fp8")
        print(json.dumps({
            "seed": seed, "program_gap": checks["max_logit_gap"]["value"],
            "control_gap": ctl, "limit": checks["max_logit_gap"]["limit"],
            "program_correct": harness.is_correct(checks),
            "control_correct": None if ctl is None else harness.is_correct(
                harness.control_checks(checks, ctl)),
            "tokens": checks["max_logit_gap"]["tokens"],
            "requests": checks["max_logit_gap"]["requests"],
            "unfinished": checks["unfinished_requests"]["value"],
            "attempted": len(run.requests), "setup_s": run.setup_s,
            "compiles_in_window": run.compiles_in_window,
            "memory_peak_bytes": run.memory_peak_bytes,
            "check_s": time.perf_counter() - t - run.setup_s - args.seconds,
        }), flush=True)


def probe(args):
    cell, device, peaks = _setup(args.workload)
    kept = {}

    class KeepingTracer(harness._Tracer):
        def summary(self):
            kept.update(devtrace.extract(self.dir))
            return super().summary()

    harness._Tracer = KeepingTracer
    run, checks, _ = harness.run_cell(cell, args.seed, args.seconds, True,
                                      t_start=T0, peaks=peaks)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(out, "wt") as f:
        json.dump(kept, f)
    for plane, lines in kept.get("planes", {}).items():
        print(f"plane {plane}: {lines}")
    names = {}
    for d in kept.get("device", []):
        if d["line"] == devtrace.MODULES_LINE:
            names[d["name"]] = names.get(d["name"], 0) + 1
    print("modules", json.dumps(names))
    print("per-layer", json.dumps(harness.read_metrics(run, cell.per_layer)))
    print("end-to-end", json.dumps(harness.read_metrics(run, cell.end_to_end)))
    if run.trace:
        print("busy_s", run.trace.busy_s, "window_s", run.trace.window_s,
              "top_ops", run.trace.top_ops(run.module_roles()), "idle_gaps", run.trace.idle_gaps)
    for line in harness.run_notes(run):
        print(line)
    print("checks", json.dumps(checks), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="mode", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--seeds", type=int, nargs="+", default=[1])
    s.add_argument("--seconds", type=float, default=30.0)
    s.add_argument("--rates", type=float, nargs="+", required=True)
    s.add_argument("--slo", type=float, default=2.0,
                   help="p90 time to first token that a sustained rate meets")
    s = sub.add_parser("limits")
    s.add_argument("--workload", required=True)
    s.add_argument("--seconds", type=float, default=10.0)
    s.add_argument("--seeds", type=int, nargs="+", required=True)
    s = sub.add_parser("probe")
    s.add_argument("--workload", required=True)
    s.add_argument("--seed", type=int, default=1)
    s.add_argument("--seconds", type=float, default=10.0)
    s.add_argument("--out", required=True)
    args = ap.parse_args()
    {"sweep": sweep, "limits": limits, "probe": probe}[args.mode](args)


if __name__ == "__main__":
    main()
