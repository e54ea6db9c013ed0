"""What every plain reference shares, whatever model it computes: the
program's weight-drawing rule, RMSNorm, the float8 control's rounding and
the matrix product it applies to, and the rows and gaps that decide
``correct``. Like the references, it imports nothing from ``repro``.

A reference module, named by a configuration file's ``"reference"`` key,
gives ``model_block(cfg)``, ``make_weights(model, seed)`` and
``logits_at(model, w, tokens, rows_b, rows_p, quant=None)``, which the
harness calls, and ``published_block(published)``, which the tests use to
hold the file's ``reduced`` list to its cuts; see ``bench/reference.py``.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0          # largest finite float8_e4m3fn


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def draw(key, shape, std, dtype):
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (x * np.float64(std)).astype(dtype)


def draw_weights(plan: Sequence[Tuple[str, Tuple[int, ...], str, float]],
                 dtype: str, seed: int) -> Dict[str, jax.Array]:
    """The weights of ``plan`` ((name, shape, init, scale) in the order the
    program's initialiser draws keys), drawn again from ``seed`` by its rule
    (``Init.param``): a truncated normal on [-2, 2] times
    ``scale / sqrt(fan_in)``, or ones, cast to ``dtype``."""
    dtype = jnp.dtype(dtype)
    key = jax.random.PRNGKey(seed)
    w = {}
    for name, shape, init, scale in plan:
        if init == "ones":
            w[name] = jnp.ones(shape, dtype)
            continue
        key, k = jax.random.split(key)
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        w[name] = draw(k, shape, float(scale / np.sqrt(max(fan_in, 1))),
                       dtype)
    return w


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def matmul(a, b, quant: Optional[str]):
    """``a @ b`` in float32 at the highest precision; ``quant="fp8"`` rounds
    the inputs to float8 e4m3 first (per-row scales for ``a``, per-column
    for ``b``)."""
    if quant == "fp8":
        a, b = fp8(a, -1), fp8(b, 0)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def served_rows(seqs: Sequence[Tuple[List[int], List[int]]], length: int):
    """Pad ``prompt + served`` sequences to ``length``; return the tokens
    and, for every served token, (row, position that predicts it, token)."""
    tokens = np.zeros((len(seqs), length), np.int32)
    rb, rp, tok = [], [], []
    for b, (prompt, served) in enumerate(seqs):
        full = list(prompt) + list(served)
        if len(full) > length:
            raise ValueError(f"sequence of {len(full)} over {length}")
        tokens[b, :len(full)] = full
        for j, t in enumerate(served):
            rb.append(b)
            rp.append(len(prompt) - 1 + j)
            tok.append(t)
    return tokens, np.asarray(rb), np.asarray(rp), np.asarray(tok)


def gaps(ref_logits, chosen) -> np.ndarray:
    """How far each chosen token's reference logit lies below the best."""
    ref = np.asarray(ref_logits, np.float64)
    chosen = np.asarray(chosen)
    return ref.max(axis=1) - ref[np.arange(len(chosen)), chosen]
