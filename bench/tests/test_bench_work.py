"""Operation and byte counts against numbers worked by hand."""
import dataclasses

import numpy as np
import pytest

from bench import reference, work

# 2 layers, d 8, 2 query heads and 1 key/value head of 4, d_ff 16, vocab 300
M = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
     "head_dim": 4, "d_ff": 16, "vocab_size": 300, "tie_embeddings": True}


def test_layer_matmul_params():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 3 x 8x16
    assert work.layer_matmul_params(M) == 64 + 64 + 64 + 384


@pytest.mark.parametrize("n, want", [
    # dense 2*3*2*576, attention 4*2*4*2*(1+2+3), unembedding 2*8*300
    (3, 6912 + 384 + 4800),
    (1, 2304 + 64 + 4800),
])
def test_prefill_flops(n, want):
    assert work.prefill_flops(M, n) == want


def test_decode_counts():
    keys = [5, 7]
    # per slot 2*2*576 + 2*8*300, attention 64 per key
    assert work.decode_flops(M, keys) == 2 * (2304 + 4800) + 64 * 12
    # weights: 2 * (2 layers * (576 + 2 norms of 8) + final norm 8
    #               + padded vocab 512 * 8), K/V: 2 bytes * 2 * 2 * 4 a token
    assert work.weight_bytes(M) == 2 * (2 * 592 + 8 + 512 * 8)
    assert work.kv_bytes_per_token(M) == 32
    assert work.decode_bytes(M, keys) == work.weight_bytes(M) + 32 * 12


@pytest.mark.parametrize("arch", ["granite-3-2b", "phi3-mini-3.8b"])
def test_weight_bytes_match_the_served_tree(arch):
    """Every parameter the program holds is read once per decode step, but
    for an untied embedding table, of which a step reads only its rows."""
    import jax
    from repro.configs import get_config
    from repro.models.common import abstract_init
    from repro.models.model import init_model

    cfg = dataclasses.replace(get_config(arch).reduced(), vocab_size=512)
    params, _ = abstract_init(init_model, cfg)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    if not cfg.tie_embeddings:
        n -= int(np.prod(params["embed"].shape))
    assert work.weight_bytes(reference.model_block(cfg)) == 2 * n
