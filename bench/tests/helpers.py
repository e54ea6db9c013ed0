"""A cell of ``BENCHMARK.json`` at a size the CPU runs in seconds."""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time

from bench import harness, traffic

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def cell_for(name: str,
             bench_json: pathlib.Path = harness.BENCHMARK_JSON
             ) -> harness.Cell:
    """A cell of ``bench_json``. A name it does not list,
    ``<config>.<mix>`` with a mix kept for a later cell, is the first listed
    cell of that configuration with the mix in place of its own."""
    try:
        return harness.find_cell(name, bench_json)
    except KeyError:
        config, mix = name.split(".", 1)
        bench = json.loads(bench_json.read_text())
        base = next(w["name"] for w in bench["workloads"]
                    if w["config"] == config)
        return dataclasses.replace(harness.find_cell(base, bench_json),
                                   name=name, mix=traffic.load_mix(mix))


def tiny(cell_name: str, max_len: int = 512,
         bench_json: pathlib.Path = harness.BENCHMARK_JSON):
    """The named cell with the program's reduced config of its model and a
    smaller ring buffer; returns (cell, cfg, model), the model block as the
    configuration's reference reads it."""
    from repro.configs import get_config
    cell = cell_for(cell_name, bench_json)
    cfg = dataclasses.replace(get_config(cell.config["arch"]).reduced(),
                              vocab_size=512)
    cell.config = dict(cell.config,
                       engine=dict(cell.config["engine"], max_len=max_len))
    return cell, cfg, cell.reference.model_block(cfg)


def run_tiny(cell_name: str, seed: int = 3, seconds: float = 1.5,
             traced: bool = False, max_len: int = 512, **kw):
    cell, cfg, model = tiny(cell_name, max_len)
    return harness.run_cell(cell, seed, seconds, traced,
                            t_start=time.perf_counter(), cfg=cfg,
                            model=model, peaks=PEAKS,
                            **kw)
