"""RWKV6 WKV recurrence Pallas TPU kernel.

The per-head recurrent state S (hd_k x hd_v, fp32) lives in VMEM scratch and
is carried across the time-chunk grid dimension (innermost, "arbitrary"),
so HBM traffic is exactly one pass over r/k/v/w plus one y write — the
memory-optimal schedule for an attention-free layer. Inside the kernel each
chunk runs a ``fori_loop`` of rank-1 state updates:

    y_t = r_t (S + u * k_t^T v_t);   S <- diag(w_t) S + k_t^T v_t

Grid = (B, H, n_chunks); hd is 64 for rwkv6-7b, so the (64, 64) state tile
is sublane/lane aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_final_ref,
                state_ref, x_ref, y_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    hd = state_ref.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1))

    def col(row):
        """(1, hd) row -> (hd, 1) column, with 2-D ops the TPU lowers."""
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    u = col(u_ref[0].astype(jnp.float32))                 # (hd, 1)

    # the chunk is upcast once into fp32 scratch: single-row loads and
    # stores at a dynamic offset need an unpacked (32-bit) layout
    for i, ref in enumerate((r_ref, k_ref, v_ref, w_ref)):
        x_ref[i] = ref[0, 0].astype(jnp.float32)

    def step(t, _):
        rt, kt, vt, wt = (x_ref[i, pl.ds(t, 1), :] for i in range(4))
        s = state_ref[...]                                # (hd, hd) fp32
        kv = col(kt) * vt                                 # rank-1 outer
        y = jnp.sum(col(rt) * (s + u * kv), axis=0, keepdims=True)
        state_ref[...] = col(wt) * s + kv
        y_ref[pl.ds(t, 1), :] = y
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)
    o_ref[0, 0] = y_ref[...].astype(o_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _final():
        s_final_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
        u: jax.Array, *, chunk: int = 64, interpret: bool = False):
    """r/k/v/w: (B, H, S, hd); u: (H, hd). Returns (y (B,H,S,hd), s (B,H,hd,hd))."""
    B, H, S, hd = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    n_chunks = S // chunk

    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    y, s_final = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            # u rides as (H, 1, hd): a (1, hd) tile spans both minor dims
            pl.BlockSpec((1, 1, hd), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), r.dtype),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32),
                        pltpu.VMEM((4, chunk, hd), jnp.float32),
                        pltpu.VMEM((chunk, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, hd))
    return y, s_final
