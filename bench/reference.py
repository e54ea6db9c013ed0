"""Plain float32 reference of the served dense decoder, independent of the
program: it imports nothing from ``repro`` and takes nothing the program
made. It draws the weights again from the run's seed, in the order and by
the rule the program's initialiser documents
(``reference_common.draw_weights``), and runs the forward pass layer by
layer in float32 at the highest matmul precision.

The architecture is the one a configuration file's ``model`` block states:
pre-norm decoder layers (RMSNorm, grouped-query attention with half-split
rotary embeddings, SwiGLU), a final RMSNorm and a tied or separate
unembedding over a vocabulary padded to a multiple of 256.

``quant="fp8"`` is the control: every matrix product of the linear layers
and the unembedding takes its inputs rounded to float8 e4m3 (per-row scales
for activations, per-column for weights), the step that would tempt a
faster build. Attention itself stays in float32.

A configuration file names this module under ``"reference"``; the harness
calls ``model_block``, ``make_weights`` and ``logits_at``
(``bench/reference_common.py`` says what each gives).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from bench.reference_common import draw_weights, matmul, rms


def model_block(cfg) -> Dict:
    """The ``model`` block a configuration file states, read off the
    attributes of the program's ``ModelConfig``; raises where that is not
    the plain dense decoder this reference computes."""
    plain = (cfg.family == "dense" and cfg.act == "swiglu" and not cfg.moe
             and not cfg.qkv_bias and not cfg.qk_norm and not cfg.kv_quant
             and cfg.sliding_window is None and cfg.attn_chunk is None
             and cfg.frontend == "none")
    if not plain:
        raise ValueError(f"{cfg.name}: not a plain dense decoder")
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim_, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size,
            "tie_embeddings": cfg.tie_embeddings,
            "rope_theta": float(cfg.rope_theta),
            "norm_eps": float(cfg.norm_eps), "dtype": cfg.dtype}


def published_block(pub: Dict) -> Dict[str, Tuple[str, object]]:
    """Per key of the ``model`` block, the key of the published
    ``config.json`` it is read from and the value there."""
    hd = pub.get("head_dim") or pub["hidden_size"] // pub[
        "num_attention_heads"]
    return {"n_layers": ("num_hidden_layers", pub["num_hidden_layers"]),
            "d_model": ("hidden_size", pub["hidden_size"]),
            "n_heads": ("num_attention_heads", pub["num_attention_heads"]),
            "n_kv_heads": ("num_key_value_heads",
                           pub["num_key_value_heads"]),
            "head_dim": ("head_dim", hd),
            "d_ff": ("intermediate_size", pub["intermediate_size"]),
            "vocab_size": ("vocab_size", pub["vocab_size"]),
            "tie_embeddings": ("tie_word_embeddings",
                               pub["tie_word_embeddings"]),
            "rope_theta": ("rope_theta", float(pub["rope_theta"])),
            "norm_eps": ("rms_norm_eps", float(pub["rms_norm_eps"])),
            "dtype": ("torch_dtype", pub["torch_dtype"])}


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


def make_weights(m: Dict, seed: int) -> Dict[str, jax.Array]:
    """The served weights, drawn again from ``seed`` in the served dtype."""
    return draw_weights(param_plan(m), m["dtype"], seed)


def _rope(x, pos, theta):
    """x (B, S, H, hd), pos (S,): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m: Dict, quant: Optional[str], q_chunk: int, x, lw):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    B, S, D = x.shape
    hd, hq, kv = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    g = hq // kv
    pos = jnp.arange(S)
    a = rms(x, f32(lw["norm1"]), m["norm_eps"])
    q = matmul(a, f32(lw["wq"]), quant).reshape(B, S, hq, hd)
    k = matmul(a, f32(lw["wk"]), quant).reshape(B, S, kv, hd)
    v = matmul(a, f32(lw["wv"]), quant).reshape(B, S, kv, hd)
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
    x = x + matmul(o, f32(lw["wo"]), quant)
    f = rms(x, f32(lw["norm2"]), m["norm_eps"])
    h = jax.nn.silu(matmul(f, f32(lw["w_gate"]), quant)) \
        * matmul(f, f32(lw["w_up"]), quant)
    return x + matmul(h, f32(lw["w_down"]), quant)


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
    h = rms(x[rows_b, rows_p], w["final_norm"].astype(jnp.float32),
            m["norm_eps"])
    wu = w["embed"].T if m["tie_embeddings"] else w["unembed"]
    return matmul(h, wu.astype(jnp.float32), quant)[:, :m["vocab_size"]]


def logits_at(m: Dict, w, tokens, rows_b, rows_p, quant: Optional[str] = None,
              q_chunk: int = 512):
    with jax.default_matmul_precision("highest"):
        return _forward_rows(tuple(sorted(m.items())), quant,
                             min(q_chunk, tokens.shape[1]), w,
                             jnp.asarray(tokens), jnp.asarray(rows_b),
                             jnp.asarray(rows_p))

