"""Flash attention (prefill) Pallas TPU kernel.

Streaming-softmax attention with explicit VMEM tiling: (block_q x d) query
tiles stay resident while (block_k x d) K/V tiles stream from HBM; the
running max / normalizer / output accumulator live in VMEM scratch across
the kv-block grid dimension (the innermost, "arbitrary" one). Causal,
sliding-window and chunked-local masking are applied inside the kernel, and
fully-masked kv blocks are skipped (no MXU work issued).

GQA is handled with *no* K/V materialisation: the K/V BlockSpec index maps
query head h -> kv head h // group.

Block sizes default to 128x128 — MXU-aligned (128 lanes, 8|16 sublanes).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  chunk: Optional[int], block_q: int, block_k: int,
                  n_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # visibility pre-check: skip blocks that are fully masked
    visible = True
    if causal:
        visible = jnp.logical_and(
            visible, k_start <= q_start + block_q - 1)
    if window is not None:
        visible = jnp.logical_and(
            visible, (q_start - (k_start + block_k - 1)) < window)
    if chunk is not None:
        visible = jnp.logical_and(
            visible, (q_start + block_q - 1) // chunk >= k_start // chunk)
        visible = jnp.logical_and(
            visible, q_start // chunk <= (k_start + block_k - 1) // chunk)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)             # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)             # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok &= kpos <= qpos
        if window is not None:
            ok &= (qpos - kpos) < window
        if chunk is not None:
            ok &= (qpos // chunk) == (kpos // chunk)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:, :1]                            # (bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)        # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # (bq, bk)
        # rows with no visible key this block: p=exp(NEG_INF - m) ~ 0, fine
        alpha = jnp.exp(m_prev - m_new)                  # (bq, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                  # fully-masked rows
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "chunk", "block_q", "block_k",
                     "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    chunk: Optional[int] = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q: (B, Hq, S, d); k/v: (B, Hkv, S, d); Hq %% Hkv == 0. -> (B, Hq, S, d)."""
    B, Hq, S, d = q.shape
    Hkv = k.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    scale = d ** -0.5

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        chunk=chunk, block_q=block_q, block_k=block_k, n_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
