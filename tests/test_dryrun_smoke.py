"""Dry-run machinery smoke test on the local (1-device) mesh.

The production 512-device sweep runs via ``python -m repro.launch.dryrun``
(XLA_FLAGS must be set before jax init); here we exercise the same
lower+compile plumbing — input specs, logical-axis shardings (incl. the
cache pytree), train/prefill/decode paths — with reduced configs on a
(1,1) mesh, so pytest needs no special device flags.
"""
import dataclasses

import jax
import pytest

from repro.configs import SHAPES, get_config
from repro.configs.shapes import ShapeSpec
from repro.distributed.sharding import single_pod_rules
from repro.launch.dryrun import (
    RULE_VARIANTS,
    _lower_cell,
    analytic_hbm_bytes,
    collective_bytes,
)

# heavy lower+compile smokes: CI's full-suite lane runs these (pytest.ini)
pytestmark = pytest.mark.slow

SMALL_SHAPES = {
    "train": ShapeSpec("train_small", 64, 4, "train"),
    "prefill": ShapeSpec("prefill_small", 64, 2, "prefill"),
    "decode": ShapeSpec("decode_small", 64, 2, "decode"),
}


def mesh11():
    return jax.make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("arch", ["granite-3-2b", "mixtral-8x22b",
                                  "rwkv6-7b", "hymba-1.5b",
                                  "seamless-m4t-large-v2"])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_lower_compile_cell(arch, kind):
    cfg = get_config(arch).reduced()
    compiled = _lower_cell(cfg, SMALL_SHAPES[kind], mesh11(),
                           single_pod_rules())
    cost = compiled.cost_analysis()
    assert float(cost.get("flops", 0)) > 0
    assert isinstance(collective_bytes(compiled.as_text()), dict)


def test_variants_lower():
    cfg = get_config("mixtral-8x22b").reduced()
    for name, (rfn, cfn) in RULE_VARIANTS.items():
        compiled = _lower_cell(cfn(cfg), SMALL_SHAPES["decode"], mesh11(),
                               rfn(single_pod_rules()))
        assert compiled is not None


def test_analytic_hbm_monotone_in_seq():
    cfg = get_config("qwen1.5-32b")
    b1 = analytic_hbm_bytes(cfg, SHAPES["decode_32k"], 256)
    small = dataclasses.replace(SHAPES["decode_32k"])
    b2 = analytic_hbm_bytes(cfg, ShapeSpec("d", 8192, 128, "decode"), 256)
    assert b1 > b2 > 0
    # int8 KV cuts decode bytes
    cfgq = dataclasses.replace(cfg, kv_quant=True)
    assert analytic_hbm_bytes(cfgq, SHAPES["decode_32k"], 256) < b1
