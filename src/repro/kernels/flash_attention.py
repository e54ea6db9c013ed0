"""Flash attention (prefill) Pallas TPU kernel.

Streaming-softmax attention with explicit VMEM tiling: a query tile stays
resident while (block_k x d) K/V tiles stream from HBM; the running max /
normalizer / output accumulator live in VMEM scratch across the kv-block grid
dimension (the innermost, "arbitrary" one), so no score tile reaches HBM.
Causal, sliding-window and chunked-local masking are applied inside the
kernel, only on blocks the mask cuts; kv blocks the mask hides entirely are
skipped, and their K/V index map points at the nearest visible block, so a
skipped block issues no DMA either.

GQA is handled with *no* K/V materialisation: the query tile holds all
``group`` query heads of one KV head ((group * block_q) x d rows), so each
K/V tile is fetched once per KV head. The MXU takes the operands in their
stored dtype (bf16 Q, K and P in serving) and accumulates in f32; the
softmax statistics and the accumulator are f32.

Block sizes default to ones chosen from S and the group size (``takes``
says which shapes those fit).
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
# default blocks: a query tile of up to 2048 rows (group x block_q), at most
# 512 positions, against 1024 keys. On a v5e, granite-3-2b's 2048-token layer
# (32 query heads over 8 KV heads of 64) ran in 0.48 ms at 512 x 1024 and at
# 1024 x 1024, the fastest of 16 block shapes tried (2.12 ms at 128 x 128,
# 0.60 ms at 2048 x 2048): smaller blocks pay per-step overhead, larger ones
# compute more of what the causal mask hides.
ROWS = 2048
BLOCK_Q = 512
BLOCK_K = 1024
# at heads over 64 those blocks need more than the 16 MiB of VMEM a kernel may
# use by default on a v5e, whose core has 128 MiB
VMEM_LIMIT = 64 * 2**20
MAX_HEAD_DIM = 256


def takes(seq_len: int, head_dim: int) -> bool:
    """Whether the kernel runs these shapes at its own blocks: a sequence
    that whole 128-row blocks tile, and heads of up to 256, whose default
    blocks are compiled for v5e within ``VMEM_LIMIT`` in the tests."""
    return (seq_len > 0 and seq_len % LANES == 0
            and 0 < head_dim <= MAX_HEAD_DIM)


def _block(seq_len: int, target: int) -> int:
    """The whole sequence if it is no longer than ``target``, else the
    largest power of two up to ``target`` that divides it."""
    if seq_len <= target:
        return seq_len
    b = 1 << (target.bit_length() - 1)
    while seq_len % b:
        b //= 2
    return b


def _kv_range(q_start, *, block_q: int, block_k: int, n_kv_blocks: int,
              causal: bool, window: Optional[int], chunk: Optional[int]):
    """First and last kv block that a query block starting at ``q_start``
    sees any key of; every block between them is visible too."""
    q_end = q_start + block_q - 1
    lo, hi = 0, n_kv_blocks - 1
    if causal:
        hi = jnp.minimum(hi, q_end // block_k)
    if window is not None:
        lo = jnp.maximum(lo, jnp.maximum(q_start - window + 1, 0) // block_k)
    if chunk is not None:
        lo = jnp.maximum(lo, (q_start // chunk) * chunk // block_k)
        hi = jnp.minimum(hi, ((q_end // chunk + 1) * chunk - 1) // block_k)
    return lo, hi


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  chunk: Optional[int], block_q: int, block_k: int,
                  n_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    group, _, d = q_ref.shape[1:]
    rows = group * block_q

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    q_end, k_end = q_start + block_q - 1, k_start + block_k - 1
    lo, hi = _kv_range(q_start, block_q=block_q, block_k=block_k,
                       n_kv_blocks=n_kv_blocks, causal=causal, window=window,
                       chunk=chunk)
    visible = jnp.logical_and(ki >= lo, ki <= hi)
    # a visible block the mask does not cut needs no mask
    whole = True
    if causal:
        whole = jnp.logical_and(whole, k_end <= q_start)
    if window is not None:
        whole = jnp.logical_and(whole, q_end - k_start < window)
    if chunk is not None:
        whole = jnp.logical_and(whole, jnp.logical_and(
            k_start // chunk == q_end // chunk,
            k_end // chunk == q_start // chunk))

    def _step(masked: bool):
        q = q_ref[0].reshape(rows, d)                    # (group*bq, d)
        k = k_ref[0, 0]                                  # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (rows, bk)
        if masked:
            # row r holds query position q_start + r %% block_q
            qpos = q_start + jax.lax.rem(
                jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0),
                block_q)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (rows, block_k), 1)
            ok = jnp.ones((rows, block_k), jnp.bool_)
            if causal:
                ok &= kpos <= qpos
            if window is not None:
                ok &= (qpos - kpos) < window
            if chunk is not None:
                ok &= (qpos // chunk) == (kpos // chunk)
            s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:, :1]                            # (rows, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row with no visible key yet has m_new = NEG_INF and p = 1; its
        # first visible key makes alpha 0, which wipes what that added
        p = jnp.exp(s - m_new)                           # (rows, bk)
        alpha = jnp.exp(m_prev - m_new)                  # (rows, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if whole is True:
        pl.when(visible)(lambda: _step(masked=False))
    else:
        pl.when(jnp.logical_and(visible, whole))(lambda: _step(masked=False))
        pl.when(jnp.logical_and(visible, jnp.logical_not(whole)))(
            lambda: _step(masked=True))

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                  # fully-masked rows
        o_ref[0] = (acc_ref[...] / l).reshape(group, block_q, d).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "chunk", "block_q", "block_k",
                     "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    chunk: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, S, d); k/v: (B, Hkv, S, d); Hq %% Hkv == 0. -> (B, Hq, S, d).

    Query head h reads KV head h // (Hq // Hkv)."""
    B, Hq, S, d = q.shape
    Hkv = k.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    block_q = min(block_q or _block(S, max(min(BLOCK_Q, ROWS // group),
                                           LANES)), S)
    block_k = min(block_k or _block(S, BLOCK_K), S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    mask = dict(causal=causal, window=window, chunk=chunk)

    def kv_index(b, h, qi, ki):
        lo, hi = _kv_range(qi * block_q, block_q=block_q, block_k=block_k,
                           n_kv_blocks=nk, **mask)
        return b, h, jnp.clip(ki, lo, hi), 0

    kernel = functools.partial(
        _flash_kernel, scale=d ** -0.5, block_q=block_q, block_k=block_k,
        n_kv_blocks=nk, **mask)
    q_spec = pl.BlockSpec((1, group, block_q, d),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d), kv_index)
    rows = group * block_q
    return pl.pallas_call(
        kernel,
        grid=(B, Hkv, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v)
