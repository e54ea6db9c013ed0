"""Plain float32 reference of the served dense decoder, independent of the
program: it imports nothing from ``repro`` and takes nothing the program
made. It draws the weights again from the run's seed, in the order and by
the rule the program's initialiser documents (``Init.param``: a truncated
normal on [-2, 2] times ``scale / sqrt(fan_in)``, cast to the served
dtype), and runs the forward pass layer by layer in float32 at the highest
matmul precision.

The architecture is the one a configuration file's ``model`` block states:
pre-norm decoder layers (RMSNorm, grouped-query attention with half-split
rotary embeddings, SwiGLU), a final RMSNorm and a tied or separate
unembedding over a vocabulary padded to a multiple of 256.

``quant="fp8"`` is the control: every matrix product of the linear layers
and the unembedding takes its inputs rounded to float8 e4m3 (per-row scales
for activations, per-column for weights), the step that would tempt a
faster build. Attention itself stays in float32.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0          # largest finite float8_e4m3fn


def padded_vocab(m: Dict) -> int:
    return -(-m["vocab_size"] // 256) * 256


def param_plan(m: Dict) -> List[Tuple[str, Tuple[int, ...], str, float]]:
    """(name, shape, init, scale) in the order the initialiser draws keys."""
    L, D, F = m["n_layers"], m["d_model"], m["d_ff"]
    hd, hq, kv = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    Vp = padded_vocab(m)
    out_scale = 1.0 / max(L, 1) ** 0.5
    plan = [("embed", (Vp, D), "normal", 1.0), ("final_norm", (D,), "ones", 0)]
    if not m["tie_embeddings"]:
        plan.append(("unembed", (D, Vp), "normal", 1.0))
    plan += [("norm1", (L, D), "ones", 0), ("norm2", (L, D), "ones", 0),
             ("wq", (L, D, hq * hd), "normal", 1.0),
             ("wk", (L, D, kv * hd), "normal", 1.0),
             ("wv", (L, D, kv * hd), "normal", 1.0),
             ("wo", (L, hq * hd, D), "normal", out_scale),
             ("w_up", (L, D, F), "normal", 1.0),
             ("w_down", (L, F, D), "normal", out_scale),
             ("w_gate", (L, D, F), "normal", 1.0)]
    return plan


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (x * np.float64(std)).astype(dtype)


def make_weights(m: Dict, seed: int) -> Dict[str, jax.Array]:
    """The served weights, drawn again from ``seed`` in the served dtype."""
    dtype = jnp.dtype(m["dtype"])
    key = jax.random.PRNGKey(seed)
    w = {}
    for name, shape, init, scale in param_plan(m):
        if init == "ones":
            w[name] = jnp.ones(shape, dtype)
            continue
        key, k = jax.random.split(key)
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        w[name] = _draw(k, shape, float(scale / np.sqrt(max(fan_in, 1))),
                        dtype)
    return w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (B, S, H, hd), pos (S,): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _matmul(a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a, -1), _fp8(b, 0)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _layer(m: Dict, quant: Optional[str], q_chunk: int, x, lw):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    B, S, D = x.shape
    hd, hq, kv = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    g = hq // kv
    pos = jnp.arange(S)
    a = _rms(x, f32(lw["norm1"]), m["norm_eps"])
    q = _matmul(a, f32(lw["wq"]), quant).reshape(B, S, hq, hd)
    k = _matmul(a, f32(lw["wk"]), quant).reshape(B, S, kv, hd)
    v = _matmul(a, f32(lw["wv"]), quant).reshape(B, S, kv, hd)
    q = _rope(q, pos, m["rope_theta"]).reshape(B, S, kv, g, hd)
    k = _rope(k, pos, m["rope_theta"])
    outs = []
    for c0 in range(0, S, q_chunk):
        qc = q[:, c0:c0 + q_chunk]
        s = jnp.einsum("bckgh,btkh->bkgct", qc, k,
                       precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
        causal = pos[None, :] <= (c0 + jnp.arange(qc.shape[1]))[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("bkgct,btkh->bckgh", p, v,
                               precision=jax.lax.Precision.HIGHEST))
    o = jnp.concatenate(outs, axis=1).reshape(B, S, hq * hd)
    x = x + _matmul(o, f32(lw["wo"]), quant)
    f = _rms(x, f32(lw["norm2"]), m["norm_eps"])
    h = jax.nn.silu(_matmul(f, f32(lw["w_gate"]), quant)) \
        * _matmul(f, f32(lw["w_up"]), quant)
    return x + _matmul(h, f32(lw["w_down"]), quant)


LAYER_KEYS = ("norm1", "norm2", "wq", "wk", "wv", "wo", "w_up", "w_down",
              "w_gate")


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _forward_rows(m_items, quant, q_chunk, w, tokens, rows_b, rows_p):
    """float32 logits (N, vocab) at positions ``(rows_b[i], rows_p[i])``."""
    m = dict(m_items)
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)

    def body(x, lw):
        return _layer(m, quant, q_chunk, x, lw), None

    x, _ = jax.lax.scan(body, x, {k: w[k] for k in LAYER_KEYS})
    h = _rms(x[rows_b, rows_p], w["final_norm"].astype(jnp.float32),
             m["norm_eps"])
    wu = w["embed"].T if m["tie_embeddings"] else w["unembed"]
    return _matmul(h, wu.astype(jnp.float32), quant)[:, :m["vocab_size"]]


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


def logits_at(m: Dict, w, tokens, rows_b, rows_p, quant: Optional[str] = None,
              q_chunk: int = 512):
    with jax.default_matmul_precision("highest"):
        return _forward_rows(tuple(sorted(m.items())), quant,
                             min(q_chunk, tokens.shape[1]), w,
                             jnp.asarray(tokens), jnp.asarray(rows_b),
                             jnp.asarray(rows_p))


def gaps(ref_logits, chosen) -> np.ndarray:
    """How far each chosen token's reference logit lies below the best."""
    ref = np.asarray(ref_logits, np.float64)
    chosen = np.asarray(chosen)
    return ref.max(axis=1) - ref[np.arange(len(chosen)), chosen]
