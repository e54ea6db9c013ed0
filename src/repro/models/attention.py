"""Attention for all families: GQA, RoPE, qk-norm, QKV bias, sliding-window,
chunked-local (llama4/iRoPE-style), cross-attention, ring-buffer decode cache.

Training/prefill attention is *chunked-query*: we scan over query chunks and
compute (chunk x S) score tiles, so the S x S score matrix is never
materialised (required for the 32K-token prefill shapes). The Pallas flash
kernel in ``repro.kernels`` is the TPU hot path; this XLA path is the
portable reference and what the dry-run lowers.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import Init, maybe_scan, rms_norm, rope

NEG_INF = -1e30


def init_attention(ini: Init, cfg: ModelConfig, n_layers: int,
                   n_q_heads: Optional[int] = None, cross: bool = False) -> Dict:
    hq = n_q_heads if n_q_heads is not None else cfg.n_attn_heads
    d, hd, kv = cfg.d_model, cfg.head_dim_, cfg.n_kv_heads
    L = (n_layers,)
    p = {
        "wq": ini.param(L + (d, hq * hd), ("layers", "embed", "heads")),
        "wk": ini.param(L + (d, kv * hd), ("layers", "embed", "kv")),
        "wv": ini.param(L + (d, kv * hd), ("layers", "embed", "kv")),
        "wo": ini.param(L + (hq * hd, d), ("layers", "heads", "embed"),
                        scale=1.0 / max(cfg.n_layers, 1) ** 0.5),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = ini.zeros(L + (hq * hd,), ("layers", "heads"))
        p["bk"] = ini.zeros(L + (kv * hd,), ("layers", "kv"))
        p["bv"] = ini.zeros(L + (kv * hd,), ("layers", "kv"))
    if cfg.qk_norm and not cross:
        p["q_norm"] = ini.ones(L + (hd,), ("layers", ""))
        p["k_norm"] = ini.ones(L + (hd,), ("layers", ""))
    return p


def _project_qkv(p: Dict, cfg: ModelConfig, x: jax.Array,
                 kv_x: Optional[jax.Array] = None):
    """Returns q (B,S,KV,G,hd), k,v (B,Skv,KV,hd)."""
    src = x if kv_x is None else kv_x
    hd, kvh = cfg.head_dim_, cfg.n_kv_heads
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", src, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", src, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q, ("batch", "seq", "act_heads"))
    hq = q.shape[-1] // hd
    g = hq // kvh
    q = q.reshape(*q.shape[:2], kvh, g, hd)
    k = k.reshape(*k.shape[:2], kvh, hd)
    v = v.reshape(*v.shape[:2], kvh, hd)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _mask(qpos: jax.Array, kpos: jax.Array, cfg: ModelConfig,
          causal: bool, local: bool = True) -> jax.Array:
    """(len(qpos), len(kpos)) additive mask in fp32. The window and chunk
    bound attention within one sequence (``local``), not cross-attention."""
    qp, kp = qpos[:, None], kpos[None, :]
    ok = jnp.ones(qp.shape[:1] + kp.shape[1:], dtype=bool)
    if causal:
        ok &= kp <= qp
    if local and cfg.sliding_window is not None:
        ok &= (qp - kp) < cfg.sliding_window
    if local and cfg.attn_chunk is not None:
        ok &= (qp // cfg.attn_chunk) == (kp // cfg.attn_chunk)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _pick_chunk(s: int, target: int = 1024) -> int:
    if s <= target:
        return s
    c = target
    while s % c:
        c //= 2
    return max(c, 1)


def attend(p: Dict, cfg: ModelConfig, x: jax.Array, *,
           causal: bool = True, kv_x: Optional[jax.Array] = None,
           use_rope: bool = True, return_kv: bool = False):
    """Full-sequence attention (train / prefill). x: (B,S,D) -> (B,S,D).

    With ``return_kv`` also returns the (roped) flat K/V (B,S,KV*hd) for
    prefill cache construction."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, kv_x=kv_x)
    Skv = k.shape[1]
    hd = cfg.head_dim_
    qpos = jnp.arange(S, dtype=jnp.int32)
    kpos = jnp.arange(Skv, dtype=jnp.int32)
    if use_rope and kv_x is None:
        q = rope(q.reshape(B, S, -1, hd), qpos, cfg.rope_theta).reshape(q.shape)
        k = rope(k, kpos, cfg.rope_theta)
    scale = hd ** -0.5

    c = _pick_chunk(S)
    n = S // c
    qc = q.reshape(B, n, c, *q.shape[2:]).transpose(1, 0, 2, 3, 4, 5)
    qposc = qpos.reshape(n, c)

    # Local-attention KV slicing: with a sliding window (or chunked-local
    # attention) each query chunk only needs a bounded KV range — slicing
    # it out (static size, dynamic start) removes the O(S^2) wasted score
    # FLOPs that full-row chunked attention pays (EXPERIMENTS §Perf it.1,
    # hymba prefill: 32x fewer attention FLOPs at window=1024, S=32K).
    kv_span = None
    if causal and kv_x is None and Skv == S:
        if cfg.sliding_window is not None:
            kv_span = min(Skv, cfg.sliding_window - 1 + c)
        elif cfg.attn_chunk is not None and cfg.attn_chunk % c == 0:
            kv_span = min(Skv, cfg.attn_chunk)

    def body(_, xs):
        qi, qpi = xs  # (B,c,KV,G,hd), (c,)
        if kv_span is None:
            ks, vs, kp = k, v, kpos
        else:
            if cfg.sliding_window is not None:
                start = qpi[0] - (kv_span - c)
            else:  # chunked-local: the enclosing attention chunk
                start = (qpi[0] // cfg.attn_chunk) * cfg.attn_chunk
            start = jnp.clip(start, 0, Skv - kv_span)
            ks = jax.lax.dynamic_slice_in_dim(k, start, kv_span, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, kv_span, axis=1)
            kp = start + jnp.arange(kv_span, dtype=jnp.int32)
        s = jnp.einsum("bckgh,btkh->bkgct", qi, ks,
                       preferred_element_type=jnp.float32) * scale
        s = s + _mask(qpi, kp, cfg, causal, kv_x is None)[None, None, None]
        w = jax.nn.softmax(s, axis=-1).astype(vs.dtype)
        o = jnp.einsum("bkgct,btkh->bckgh", w, vs)
        return None, o

    _, out = maybe_scan(body, None, (qc, qposc), unroll=cfg.unroll)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, -1)
    out = constrain(out, ("batch", "seq", "act_heads"))
    proj = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    if return_kv:
        return proj, (k.reshape(B, Skv, -1), v.reshape(B, Skv, -1))
    return proj


def pack_ring(kv: jax.Array, cache_len: int) -> jax.Array:
    """Place a prefilled K/V sequence (B,S,F) into its ring-buffer slots
    (token t -> slot t %% C), keeping only the last ``cache_len`` tokens."""
    B, S, F = kv.shape
    C = cache_len
    if S == C:
        return kv
    if S > C:
        tail = kv[:, S - C:]
        return jnp.roll(tail, S % C, axis=1)
    pad = jnp.zeros((B, C - S, F), kv.dtype)
    return jnp.concatenate([kv, pad], axis=1)


# ---------------------------------------------------------------------------
# int8 KV quantization (per-token-per-head symmetric)
# ---------------------------------------------------------------------------

def quantize_kv(x: jax.Array, n_kv_heads: int):
    """x: (..., KVH*hd) -> (int8 codes same shape, scales (..., KVH))."""
    hd = x.shape[-1] // n_kv_heads
    xr = x.reshape(x.shape[:-1] + (n_kv_heads, hd)).astype(jnp.float32)
    scale = jnp.max(jnp.abs(xr), axis=-1) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xr / scale[..., None]), -127, 127)
    return (q.astype(jnp.int8).reshape(x.shape),
            scale.astype(x.dtype))


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Inverse of quantize_kv; returns (..., KVH*hd) in ``dtype``."""
    kvh = scale.shape[-1]
    hd = q.shape[-1] // kvh
    # the scales are widened to the codes' (..., KVH*hd) by an exact 0/1
    # product: splitting the codes into (KVH, hd), or a repeat of the scales,
    # has the TPU compiler relayout the whole int8 ring
    widen = jnp.repeat(jnp.eye(kvh, dtype=jnp.float32), hd, axis=-1)
    s = jnp.einsum("...k,kf->...f", scale.astype(jnp.float32), widen,
                   precision="highest")
    return (q.astype(jnp.float32) * s).astype(dtype)


# ---------------------------------------------------------------------------
# Decode path (ring-buffer KV cache)
# ---------------------------------------------------------------------------

def decode_attend(p: Dict, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
                  ring: Dict[str, jax.Array]):
    """One-token attention against the ring-buffer cache, which it only reads.

    x: (B,1,D); pos: (B,) tokens generated so far; ring: ``k``/``v``
    (B,C,KV*hd), token t in slot t %% C (int8 when cfg.kv_quant, with
    per-token-per-head ``k_scale``/``v_scale`` (B,C,KV)). The new token goes
    to slot pos %% C: that slot's old entry is masked, and the new key and
    value join the softmax beside the ring. Returns (out, rows): ``rows``
    holds each ring leaf's new entry (B, ...), for the caller to write at
    slot pos %% C."""
    B, _, _ = x.shape
    C = ring["k"].shape[1]
    hd, kvh = cfg.head_dim_, cfg.n_kv_heads
    q, k_new, v_new = _project_qkv(p, cfg, x)
    q = rope(q.reshape(B, 1, -1, hd), pos[:, None], cfg.rope_theta).reshape(q.shape)
    k_new = rope(k_new, pos[:, None], cfg.rope_theta)

    rows = {"k": k_new[:, 0].reshape(B, -1), "v": v_new[:, 0].reshape(B, -1)}
    if cfg.kv_quant:
        rows["k"], rows["k_scale"] = quantize_kv(rows["k"], kvh)
        rows["v"], rows["v_scale"] = quantize_kv(rows["v"], kvh)
        kc = dequantize_kv(ring["k"], ring["k_scale"], x.dtype)
        vc = dequantize_kv(ring["v"], ring["v_scale"], x.dtype)
        kn = dequantize_kv(rows["k"], rows["k_scale"], x.dtype)
        vn = dequantize_kv(rows["v"], rows["v_scale"], x.dtype)
    else:
        kc, vc, kn, vn = ring["k"], ring["v"], rows["k"], rows["v"]
    kn, vn = kn.reshape(B, kvh, hd), vn.reshape(B, kvh, hd)

    # once this token is written, slot j holds position
    # pslot[j] = pos - ((pos - j) mod C), the cache holding (pos-C, pos]; the
    # ring's slot pos %% C (pslot == pos) still holds pos - C and is masked.
    # A slot is valid iff 0 <= pslot < pos and within the window/chunk of pos.
    j = jnp.arange(C, dtype=jnp.int32)[None, :]
    pnow = pos[:, None].astype(jnp.int32)
    pslot = pnow - jnp.mod(pnow - j, C)
    ok = (pslot >= 0) & (pslot < pnow)
    if cfg.sliding_window is not None:
        ok &= (pnow - pslot) < cfg.sliding_window
    if cfg.attn_chunk is not None:
        ok &= (pslot // cfg.attn_chunk) == (pnow // cfg.attn_chunk)
    mask = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)  # (B,C)

    # The ring is read in its stored (C, KV*hd) layout: each query head
    # (q from _project_qkv is (B,1,KV,G,hd)) becomes a row of KV*hd that is
    # zero outside its own KV head, and of the (Q, KV*hd) output each head
    # keeps its own block. Reshaping the ring to (C, KV, hd) instead costs a
    # relayout of every layer's slab.
    G = q.shape[3]
    f32 = jnp.float32
    scale = hd ** -0.5
    eye = jnp.eye(kvh, dtype=q.dtype)
    qf = (q[:, 0, :, :, None, :] * eye[None, :, None, :, None]
          ).reshape(B, kvh * G, kvh * hd)
    s = jnp.einsum("btf,bqf->btq", kc, qf, preferred_element_type=f32) * scale
    s = s + mask[:, :, None]                                       # (B,C,Q)
    s_new = jnp.einsum("bkgh,bkh->bkg", q[:, 0], kn,
                       preferred_element_type=f32).reshape(B, 1, -1) * scale
    # softmax over the ring's C columns and the new token's one
    m = jnp.maximum(jnp.max(s, axis=1, keepdims=True), s_new)
    e, e_new = jnp.exp(s - m), jnp.exp(s_new - m)
    den = jnp.sum(e, axis=1, keepdims=True) + e_new
    w, w_new = (e / den).astype(vc.dtype), (e_new / den).astype(vc.dtype)
    of = jnp.einsum("btq,btf->bqf", w, vc, preferred_element_type=f32)
    o = jnp.moveaxis(jnp.diagonal(of.reshape(B, kvh, G, kvh, hd),
                                  axis1=1, axis2=3), -1, 1)    # (B,KV,G,hd)
    o = o + (w_new.reshape(B, kvh, G, 1).astype(f32)
             * vn[:, :, None, :].astype(f32))
    o = o.astype(x.dtype).reshape(B, 1, -1)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"]), rows


def cross_decode_attend(p: Dict, cfg: ModelConfig, x: jax.Array,
                        cross_k: jax.Array, cross_v: jax.Array) -> jax.Array:
    """Decoder cross-attention against precomputed encoder KV.

    x: (B,1,D); cross_k/v: (B,S_enc,KV*hd).
    """
    B = x.shape[0]
    hd, kvh = cfg.head_dim_, cfg.n_kv_heads
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, 1, kvh, -1, hd)
    kc = cross_k.reshape(B, cross_k.shape[1], kvh, hd)
    vc = cross_v.reshape(B, cross_v.shape[1], kvh, hd)
    s = jnp.einsum("bkgh,btkh->bkgt", q[:, 0], kc,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    w = jax.nn.softmax(s, axis=-1).astype(vc.dtype)
    o = jnp.einsum("bkgt,btkh->bkgh", w, vc).reshape(B, 1, -1)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"])


def cross_kv(p: Dict, cfg: ModelConfig, memory: jax.Array):
    """Precompute cross-attention K/V from encoder memory (B,S_enc,D)."""
    k = jnp.einsum("bsd,dh->bsh", memory, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", memory, p["wv"])
    return k, v
