"""Single-token decode attention Pallas TPU kernel.

Decode is HBM-bandwidth bound: the whole KV cache streams through VMEM once
per step while the query row stays resident. Grid = (B, Hkv, n_kv_blocks)
with the kv-block dimension innermost ("arbitrary") carrying the streaming
softmax state in VMEM scratch. All q heads of one KV group (GQA) are
processed together as a (group x d) tile — turning the memory-bound dot
into a small MXU matmul and amortising each KV byte across the group.

Masking covers the ring-buffer layout: slot j holds position
``pos - ((pos - j) mod C)``; slots outside [pos-window, pos] (or the current
attention chunk) are masked.
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


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   scale: float, window: Optional[int],
                   chunk: Optional[int], block_k: int, n_kv_blocks: int,
                   cache_len: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (group, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    pos = pos_ref[pl.program_id(0)]                      # () current position
    j = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    pslot = pos - jax.lax.rem(pos - j + cache_len * 2, cache_len)
    ok = pslot >= 0
    if window is not None:
        ok &= (pos - pslot) < window
    if chunk is not None:
        ok &= (pslot // chunk) == (pos // chunk)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "chunk", "block_k", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, *, window: Optional[int] = None,
                     chunk: Optional[int] = None, block_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, Hq, d); k/v: (B, Hkv, C, d) ring buffers; pos: (B,) int32.

    Returns (B, Hq, d). Ring layout: token t lives in slot t %% C and the
    current token's K/V must already be written at slot pos %% C.
    """
    B, Hq, d = q.shape
    _, Hkv, C, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    block_k = min(block_k, C)
    assert C % block_k == 0, (C, block_k)
    nk = C // block_k
    scale = d ** -0.5
    qg = q.reshape(B, Hkv, group, d)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, chunk=chunk,
        block_k=block_k, n_kv_blocks=nk, cache_len=C)

    # pos (B,) is scalar-prefetched into SMEM whole: the kernel gets it as
    # its first ref, each index map as a trailing argument
    q_spec = pl.BlockSpec((1, 1, group, d), lambda b, h, ki, _: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b, h, ki, _: (b, h, ki, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos.astype(jnp.int32), qg, k, v)
    return out.reshape(B, Hq, d)
