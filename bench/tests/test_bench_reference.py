"""The plain reference against the program's own steps at a tiny size."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.reference_common import gaps, served_rows

ARCHS = ["granite-3-2b", "phi3-mini-3.8b"]   # tied GQA, untied MHA


def _tiny(arch):
    from repro.configs import get_config
    return dataclasses.replace(get_config(arch).reduced(), vocab_size=512)


@pytest.mark.parametrize("arch", ARCHS)
def test_weights_drawn_again_match_the_program(arch):
    from repro.launch.serve import build_engine
    cfg = _tiny(arch)
    seed = 2 ** 31 + 12345          # seeds may run past 32 signed bits
    eng = build_engine(cfg, max_batch=2, max_len=64, seed=seed % 2 ** 32)
    w = reference.make_weights(reference.model_block(cfg), seed % 2 ** 32)
    p = eng.params
    flat = {"embed": p["embed"], "final_norm": p["final_norm"],
            **{k: p["dec"][k] for k in ("norm1", "norm2")},
            **p["dec"]["attn"], **p["dec"]["mlp"]}
    if "unembed" in p:
        flat["unembed"] = p["unembed"]
    assert sorted(flat) == sorted(w)
    for k in w:
        np.testing.assert_array_equal(np.asarray(w[k]), np.asarray(flat[k]),
                                      err_msg=k)


@pytest.mark.parametrize("arch", ARCHS)
def test_reference_matches_prefill_then_decode(arch):
    """Right-padded bucketed prefill and ring-buffer decode, as the engine
    runs them, against the reference's full forward pass."""
    from repro.launch.serve import build_engine
    from repro.models.model import decode_step, prefill_step
    cfg = _tiny(arch)
    m = reference.model_block(cfg)
    eng = build_engine(cfg, max_batch=1, max_len=64, seed=7)
    prompt = list(np.random.default_rng(0).integers(0, 256, 13))
    cache, logits = jax.jit(functools.partial(prefill_step, cfg, max_len=64))(
        eng.params, {"tokens": jnp.asarray([prompt + [0] * 3], jnp.int32)},
        true_lens=jnp.asarray([13], jnp.int32))
    decode = jax.jit(functools.partial(decode_step, cfg))
    served, got = [], []
    for _ in range(6):
        row = np.asarray(logits[0, -1, :cfg.vocab_size], np.float32)
        got.append(row)
        served.append(int(row.argmax()))
        logits, cache = decode(eng.params,
                               jnp.asarray([[served[-1]]], jnp.int32), cache)
    w = reference.make_weights(m, 7)
    rows = served_rows([(prompt, served)], 32)
    ref = np.asarray(reference.logits_at(m, w, *rows[:3]))
    got = np.stack(got)
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() / scale < 0.02
    assert gaps(ref, served).max() < 0.02 * scale


def test_fp8_control_moves_the_logits():
    cfg = _tiny("granite-3-2b")
    m = reference.model_block(cfg)
    w = reference.make_weights(m, 3)
    prompt = list(np.random.default_rng(1).integers(0, 256, 40))
    rows = served_rows([(prompt, [1] * 20)], 64)
    ref = np.asarray(reference.logits_at(m, w, *rows[:3]))
    low = np.asarray(reference.logits_at(m, w, *rows[:3], quant="fp8"))
    assert np.abs(low - ref).max() > 1e-3 * np.abs(ref).max()
    assert gaps(ref, low.argmax(1)).max() > 0


def test_served_rows_positions():
    tokens, rb, rp, tok = served_rows([([5, 6, 7], [8, 9]),
                                                 ([1], [2])], 8)
    assert tokens.tolist() == [[5, 6, 7, 8, 9, 0, 0, 0],
                               [1, 2, 0, 0, 0, 0, 0, 0]]
    # token 8 is predicted at position 2, token 9 at 3, token 2 at 0
    assert list(zip(rb, rp, tok)) == [(0, 2, 8), (0, 3, 9), (1, 0, 2)]
