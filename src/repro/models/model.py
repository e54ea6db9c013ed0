"""Unified composable model covering all assigned families.

One parameter pytree + three entry points:

    init_model(ini, cfg)                      -> Boxed param tree
    forward(cfg, params, batch)               -> final hidden states (train)
    loss_fn(cfg, params, batch)               -> (scalar, metrics)
    prefill_step(cfg, params, batch)          -> (cache, last-token logits)
    decode_step(cfg, params, tokens, cache)   -> (logits, cache')

Layers are stacked along a leading ``layers`` dim and executed with
``lax.scan`` (small HLO, fast compile at 56+ layers). MoE interleaving
(llama4: dense/MoE alternation) scans over super-layers of ``interleave``
sublayers so the alternating order is preserved inside one homogeneous scan.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import effective_cache_len
from repro.distributed.sharding import constrain
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import mlp_moe, rwkv
from repro.models.common import Boxed, Init, maybe_scan, rms_norm

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_model(ini: Init, cfg: ModelConfig) -> Dict:
    L, D = cfg.n_layers, cfg.d_model
    k = cfg.moe.interleave if cfg.moe else 1
    n_moe = L // k if cfg.moe else 0
    n_dense = L - n_moe

    p: Dict = {
        "embed": ini.param((cfg.padded_vocab, D), ("vocab", "embed")),
        "final_norm": ini.ones((D,), ("embed",)),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = ini.param((D, cfg.padded_vocab), ("embed", "vocab"))

    dec: Dict = {
        "norm1": ini.ones((L, D), ("layers", "embed")),
        "norm2": ini.ones((L, D), ("layers", "embed")),
    }
    if cfg.family == "ssm":
        dec["tm"] = rwkv.init_time_mix(ini, cfg, L)
        dec["cm"] = rwkv.init_channel_mix(ini, cfg, L)
    else:
        dec["attn"] = attn_mod.init_attention(ini, cfg, L)
        if cfg.family == "hybrid":
            dec["ssm"] = mamba_mod.init_mamba(ini, cfg, L)
        if n_dense:
            dec["mlp"] = mlp_moe.init_mlp(ini, cfg, n_dense)
        if n_moe:
            dec["moe"] = mlp_moe.init_moe(ini, cfg, n_moe)
    if cfg.is_encdec:
        dec["cross"] = attn_mod.init_attention(ini, cfg, L, cross=True)
        dec["norm3"] = ini.ones((L, D), ("layers", "embed"))
    p["dec"] = dec

    if cfg.is_encdec:
        Le = cfg.n_encoder_layers
        p["enc"] = {
            "attn": attn_mod.init_attention(ini, cfg, Le),
            "mlp": mlp_moe.init_mlp(ini, cfg, Le),
            "norm1": ini.ones((Le, D), ("layers", "embed")),
            "norm2": ini.ones((Le, D), ("layers", "embed")),
            "final_norm": ini.ones((D,), ("embed",)),
        }
    if cfg.frontend == "audio_frames":
        p["frame_proj"] = ini.param((D, D), ("embed", "act_embed"))
    if cfg.frontend == "vision_patches":
        p["patch_proj"] = ini.param((D, D), ("embed", "act_embed"))
    return p


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def _remat(body, cfg: ModelConfig):
    """Layer rematerialisation. "block" recomputes everything (min memory,
    but re-executes the FSDP weight gathers in backward); "dots" saves
    matmul outputs so neither the matmuls nor their operand gathers are
    recomputed (more live memory, fewer collective bytes — §Perf)."""
    if cfg.remat == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body)


def _regroup(tree, n_super: int, k: int):
    """Reshape stacked leaves (n_super*k, ...) -> (n_super, k, ...)."""
    return jax.tree.map(
        lambda a: a.reshape((n_super, k) + a.shape[1:]), tree)


def _idx(tree, j: int):
    return jax.tree.map(lambda a: a[j], tree)


def _embed_tokens(cfg: ModelConfig, p: Dict, batch: Dict) -> jax.Array:
    x = jnp.take(p["embed"], batch["tokens"], axis=0)
    if cfg.frontend == "vision_patches" and "patches" in batch:
        vis = batch["patches"] @ p["patch_proj"]
        x = jnp.concatenate([vis, x], axis=1)
    return constrain(x, ("batch", "seq", "act_embed"))


def _unembed(cfg: ModelConfig, p: Dict, h: jax.Array) -> jax.Array:
    w = p["embed"].T if cfg.tie_embeddings else p["unembed"]
    logits = jnp.einsum("bsd,dv->bsv", h, w, preferred_element_type=jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    return logits


# ---------------------------------------------------------------------------
# Full-sequence stacks (train / prefill)
# ---------------------------------------------------------------------------

def _ffn(cfg: ModelConfig, lp: Dict, j: int, k: int, x: jax.Array,
         aux: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Sublayer j's FFN: MoE on the last sublayer of a super-layer."""
    if cfg.moe and j == k - 1:
        mp = lp["moe"]
        aux = aux + mlp_moe.moe_aux_loss(mp, cfg, x)
        return mlp_moe.moe(mp, cfg, x), aux
    return mlp_moe.mlp(_idx(lp["mlp"], j), cfg, x), aux


def _lm_stack_full(cfg: ModelConfig, dec: Dict, x: jax.Array, *,
                   memory: Optional[jax.Array], collect_cache: bool,
                   cache_len: int, remat: bool):
    """Decoder stack over the full sequence.

    Returns (hidden, aux_loss, per-layer cache pytree or None).
    """
    L = cfg.n_layers
    k = cfg.moe.interleave if cfg.moe else 1
    n_super = L // k

    xs = {
        "attn": _regroup(dec["attn"], n_super, k),
        "norm1": _regroup(dec["norm1"], n_super, k),
        "norm2": _regroup(dec["norm2"], n_super, k),
    }
    if cfg.moe:
        xs["moe"] = dec["moe"]  # (n_super, ...)
        if "mlp" in dec:
            xs["mlp"] = _regroup(dec["mlp"], n_super, k - 1)
    else:
        xs["mlp"] = _regroup(dec["mlp"], n_super, k)
    if cfg.family == "hybrid":
        xs["ssm"] = _regroup(dec["ssm"], n_super, k)
    if cfg.is_encdec:
        xs["cross"] = _regroup(dec["cross"], n_super, k)
        xs["norm3"] = _regroup(dec["norm3"], n_super, k)

    def body(carry, lp):
        x, aux = carry
        ys = []
        for j in range(k):
            a_in = rms_norm(x, _idx(lp["norm1"], j), cfg.norm_eps)
            ap = _idx(lp["attn"], j)
            if collect_cache:
                a_out, (kk, vv) = attn_mod.attend(ap, cfg, a_in, return_kv=True)
                rk = attn_mod.pack_ring(kk, cache_len)
                rv = attn_mod.pack_ring(vv, cache_len)
                if cfg.kv_quant:
                    qk, sk = attn_mod.quantize_kv(rk, cfg.n_kv_heads)
                    qv, sv = attn_mod.quantize_kv(rv, cfg.n_kv_heads)
                    y = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
                else:
                    y = {"k": rk, "v": rv}
            else:
                a_out = attn_mod.attend(ap, cfg, a_in)
                y = {}
            if cfg.family == "hybrid":
                m_out, s_f, conv_carry = mamba_mod.mamba_mix(
                    _idx(lp["ssm"], j), cfg, a_in,
                    jnp.zeros((x.shape[0], cfg.n_ssm_heads, cfg.ssm.head_dim,
                               cfg.ssm.state_size), jnp.float32))
                a_out = a_out + m_out
                if collect_cache:
                    y["ssm_state"] = s_f
                    if cfg.ssm.conv_width > 1:
                        y["conv_state"] = conv_carry
            x = x + a_out
            if cfg.is_encdec:
                c_in = rms_norm(x, _idx(lp["norm3"], j), cfg.norm_eps)
                cp = _idx(lp["cross"], j)
                x = x + attn_mod.attend(cp, cfg, c_in, causal=False,
                                        kv_x=memory, use_rope=False)
                if collect_cache:
                    ck, cv = attn_mod.cross_kv(cp, cfg, memory)
                    y["cross_k"], y["cross_v"] = ck, cv
            f_in = rms_norm(x, _idx(lp["norm2"], j), cfg.norm_eps)
            f_out, aux = _ffn(cfg, lp, j, k, f_in, aux)
            x = x + f_out
            ys.append(y)
        x = constrain(x, ("batch", "seq", "act_embed"))
        # stack sublayer cache slices -> leading dim k
        ys_st = jax.tree.map(lambda *a: jnp.stack(a), *ys) if ys[0] else None
        return (x, aux), ys_st

    if remat:
        body = _remat(body, cfg)
    (x, aux), cache_st = maybe_scan(body, (x, jnp.zeros((), jnp.float32)), xs,
                                    unroll=cfg.unroll)
    if collect_cache and cache_st is not None:
        # (n_super, k, ...) -> (L, ...)
        cache_st = jax.tree.map(
            lambda a: a.reshape((L,) + a.shape[2:]), cache_st)
    return x, aux, cache_st


def _rwkv_stack_full(cfg: ModelConfig, dec: Dict, x: jax.Array, *,
                     collect_cache: bool, remat: bool):
    B = x.shape[0]
    H, hd = cfg.n_ssm_heads, cfg.ssm.head_dim
    xs = {"tm": dec["tm"], "cm": dec["cm"],
          "norm1": dec["norm1"], "norm2": dec["norm2"]}

    def body(carry, lp):
        x, aux = carry
        s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
        shift0 = jnp.zeros((B, cfg.d_model), x.dtype)
        a_in = rms_norm(x, lp["norm1"], cfg.norm_eps)
        tm_out, tm_shift, s_f = rwkv.time_mix(lp["tm"], cfg, a_in, shift0, s0)
        x = x + tm_out
        c_in = rms_norm(x, lp["norm2"], cfg.norm_eps)
        cm_out, cm_shift = rwkv.channel_mix(lp["cm"], cfg, c_in, shift0)
        x = x + cm_out
        y = ({"ssm_state": s_f, "shift_tm": tm_shift, "shift_cm": cm_shift}
             if collect_cache else None)
        return (x, aux), y

    if remat:
        body = _remat(body, cfg)
    (x, aux), cache_st = maybe_scan(body, (x, jnp.zeros((), jnp.float32)), xs,
                                    unroll=cfg.unroll)
    return x, aux, cache_st


def _encoder(cfg: ModelConfig, p: Dict, frames: jax.Array) -> jax.Array:
    x = frames @ p["frame_proj"]
    enc = p["enc"]
    xs = {"attn": enc["attn"], "mlp": enc["mlp"],
          "norm1": enc["norm1"], "norm2": enc["norm2"]}

    def body(x, lp):
        a_in = rms_norm(x, lp["norm1"], cfg.norm_eps)
        x = x + attn_mod.attend(lp["attn"], cfg, a_in, causal=False)
        f_in = rms_norm(x, lp["norm2"], cfg.norm_eps)
        x = x + mlp_moe.mlp(lp["mlp"], cfg, f_in)
        return x, None

    x, _ = maybe_scan(body, x, xs, unroll=cfg.unroll)
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


def forward(cfg: ModelConfig, params: Dict, batch: Dict, *,
            is_train: bool = True, collect_cache: bool = False,
            cache_len: int = 0):
    """Hidden states (B,S,D) after final norm (+ aux loss, + prefill cache)."""
    memory = None
    if cfg.is_encdec:
        memory = _encoder(cfg, params, batch["frames"])
    x = _embed_tokens(cfg, params, batch)
    remat = is_train and cfg.remat != "none"
    if cfg.family == "ssm":
        h, aux, cache = _rwkv_stack_full(cfg, params["dec"], x,
                                         collect_cache=collect_cache,
                                         remat=remat)
    else:
        h, aux, cache = _lm_stack_full(cfg, params["dec"], x, memory=memory,
                                       collect_cache=collect_cache,
                                       cache_len=cache_len, remat=remat)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return h, aux, cache


# ---------------------------------------------------------------------------
# Loss (chunked cross-entropy: never materialises fp32 (B,S,V) logits)
# ---------------------------------------------------------------------------

def chunked_xent(cfg: ModelConfig, params: Dict, h: jax.Array,
                 targets: jax.Array, chunk: int = 512):
    B, S, D = h.shape
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    c = chunk if S % chunk == 0 else S
    n = S // c
    hc = h.reshape(B, n, c, D).swapaxes(0, 1)
    tc = targets.reshape(B, n, c).swapaxes(0, 1)
    pad_mask = (jnp.arange(cfg.padded_vocab) >= cfg.vocab_size) * (-1e30)

    def body(acc, xs):
        hh, tt = xs
        logits = jnp.einsum("bcd,dv->bcv", hh, w,
                            preferred_element_type=jnp.float32) + pad_mask
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tt[..., None], axis=-1)[..., 0]
        loss = jnp.sum(lse - gold)
        correct = jnp.sum(jnp.argmax(logits, -1) == tt)
        return (acc[0] + loss, acc[1] + correct), None

    (loss, correct), _ = maybe_scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (hc, tc), unroll=cfg.unroll)
    ntok = B * S
    return loss / ntok, correct / ntok


def loss_fn(cfg: ModelConfig, params: Dict, batch: Dict,
            aux_weight: float = 0.01):
    h, aux, _ = forward(cfg, params, batch, is_train=True)
    if cfg.frontend == "vision_patches" and "patches" in batch:
        h = h[:, batch["patches"].shape[1]:, :]
    # keep the backward residual stream in model dtype (see grad_cast)
    from repro.models.common import grad_cast
    loss, acc = chunked_xent(cfg, params, grad_cast(h, cfg.jnp_dtype),
                             batch["targets"])
    total = loss + aux_weight * aux
    return total, {"loss": loss, "aux_loss": aux, "accuracy": acc}


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------

def prefill_step(cfg: ModelConfig, params: Dict, batch: Dict,
                 max_len: Optional[int] = None,
                 true_lens: Optional[jax.Array] = None):
    """Run the prompt, return (cache, last-token logits).

    ``true_lens`` (B,) supports right-padded prompts (serving engine
    bucketing): logits are taken at each row's true last token and the
    decode position starts there — padded ring slots are provably masked
    at decode because their slot position exceeds ``pos``.
    """
    if cfg.is_encdec:
        S = batch["tokens"].shape[1] + batch["frames"].shape[1]
    else:
        S = batch["tokens"].shape[1]
        if cfg.frontend == "vision_patches" and "patches" in batch:
            S += batch["patches"].shape[1]
    C = effective_cache_len(cfg, max_len or S)
    h, _, cache = forward(cfg, params, batch, is_train=False,
                          collect_cache=True, cache_len=C)
    B = h.shape[0]
    cache = dict(cache or {})
    n_dec_tokens = batch["tokens"].shape[1] if cfg.is_encdec else S
    if true_lens is None:
        pos = jnp.full((B,), n_dec_tokens, jnp.int32)
        logits = _unembed(cfg, params, h[:, -1:, :])
    else:
        pos = true_lens.astype(jnp.int32)
        offset = 0
        if cfg.frontend == "vision_patches" and "patches" in batch:
            offset = batch["patches"].shape[1]
        idx = jnp.clip(true_lens - 1 + offset, 0, h.shape[1] - 1)
        logits = _unembed(cfg, params,
                          h[jnp.arange(B), idx][:, None, :])
    cache["pos"] = pos
    return cache, logits


def decode_step(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                cache: Dict):
    """One decode step for the whole batch. tokens: (B,1)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    x = constrain(x, ("batch", "seq", "act_embed"))
    pos = cache["pos"]
    dec = params["dec"]
    L = cfg.n_layers
    k = cfg.moe.interleave if cfg.moe else 1
    n_super = L // k

    if cfg.family == "ssm":
        xs = ({"tm": dec["tm"], "cm": dec["cm"], "norm1": dec["norm1"],
               "norm2": dec["norm2"]},
              {"ssm_state": cache["ssm_state"], "shift_tm": cache["shift_tm"],
               "shift_cm": cache["shift_cm"]})

        def body(x, xs_i):
            lp, lc = xs_i
            a_in = rms_norm(x, lp["norm1"], cfg.norm_eps)
            tm_out, tm_shift, s = rwkv.time_mix_step(
                lp["tm"], cfg, a_in, lc["shift_tm"], lc["ssm_state"])
            x = x + tm_out
            c_in = rms_norm(x, lp["norm2"], cfg.norm_eps)
            cm_out, cm_shift = rwkv.channel_mix(
                lp["cm"], cfg, c_in, lc["shift_cm"])
            x = x + cm_out
            return x, {"ssm_state": s, "shift_tm": tm_shift,
                       "shift_cm": cm_shift}

        x, new_c = maybe_scan(body, x, xs, unroll=cfg.unroll)
        new_cache = dict(cache)
        new_cache.update(new_c)
        new_cache["pos"] = pos + 1
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return _unembed(cfg, params, h), new_cache

    # attention families
    lp_xs = {
        "attn": _regroup(dec["attn"], n_super, k),
        "norm1": _regroup(dec["norm1"], n_super, k),
        "norm2": _regroup(dec["norm2"], n_super, k),
    }
    if cfg.moe:
        lp_xs["moe"] = dec["moe"]
        if "mlp" in dec:
            lp_xs["mlp"] = _regroup(dec["mlp"], n_super, k - 1)
    else:
        lp_xs["mlp"] = _regroup(dec["mlp"], n_super, k)
    if cfg.family == "hybrid":
        lp_xs["ssm"] = _regroup(dec["ssm"], n_super, k)
    if cfg.is_encdec:
        lp_xs["cross"] = _regroup(dec["cross"], n_super, k)
        lp_xs["norm3"] = _regroup(dec["norm3"], n_super, k)

    # the ring (and cross-attention K/V) is read-only inside the layer scan:
    # each layer emits only its B new rows, written after the scan
    ring_keys = [kk for kk in ("k", "v", "k_scale", "v_scale") if kk in cache]
    lc_xs = {kk: vv.reshape((n_super, k) + vv.shape[1:])
             for kk, vv in cache.items() if kk != "pos"}

    def body(carry, xs_i):
        x, aux = carry
        lp, lc = xs_i
        ys = {kk: [] for kk in lc if not kk.startswith("cross_")}
        for j in range(k):
            a_in = rms_norm(x, _idx(lp["norm1"], j), cfg.norm_eps)
            a_out, rows = attn_mod.decode_attend(
                _idx(lp["attn"], j), cfg, a_in, pos,
                {kk: lc[kk][j] for kk in ring_keys})
            for kk, row in rows.items():
                ys[kk].append(row)
            if cfg.family == "hybrid":
                cw = cfg.ssm.conv_width
                if cw > 1:
                    m_out, s2, cc2 = mamba_mod.mamba_step(
                        _idx(lp["ssm"], j), cfg, a_in,
                        lc["ssm_state"][j], lc["conv_state"][j])
                    ys["conv_state"].append(cc2)
                else:
                    m_out, s2, _ = mamba_mod.mamba_step(
                        _idx(lp["ssm"], j), cfg, a_in, lc["ssm_state"][j],
                        None)
                ys["ssm_state"].append(s2)
                a_out = a_out + m_out
            x = x + a_out
            if cfg.is_encdec:
                c_in = rms_norm(x, _idx(lp["norm3"], j), cfg.norm_eps)
                x = x + attn_mod.cross_decode_attend(
                    _idx(lp["cross"], j), cfg, c_in,
                    lc["cross_k"][j], lc["cross_v"][j])
            f_in = rms_norm(x, _idx(lp["norm2"], j), cfg.norm_eps)
            f_out, aux = _ffn(cfg, lp, j, k, f_in, aux)
            x = x + f_out
        return (x, aux), {kk: jnp.stack(vv) for kk, vv in ys.items()}

    (x, _), new_c = maybe_scan(body, (x, jnp.zeros((), jnp.float32)),
                               (lp_xs, lc_xs), unroll=cfg.unroll)
    new_c = {kk: vv.reshape((L,) + vv.shape[2:]) for kk, vv in new_c.items()}
    # every layer's new rows go to slot pos % C of their slot's ring: a
    # scatter of L*B rows, in place where the caller donates the cache
    # (one index per layer and slot keeps the cache's layout for the write)
    lidx = jnp.arange(L)[:, None]
    bidx = jnp.arange(pos.shape[0])[None, :]
    slot = (pos % cache["k"].shape[2])[None, :]
    new_cache = dict(cache)
    for kk, vv in new_c.items():
        new_cache[kk] = (cache[kk].at[lidx, bidx, slot].set(vv)
                         if kk in ring_keys else vv)
    new_cache["pos"] = pos + 1
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _unembed(cfg, params, h), new_cache
