"""Fused RMSNorm Pallas TPU kernel (memory-bound elementwise + reduction).

One (rows x d) tile per grid step: read once, rsqrt-normalise in fp32,
scale, write once — fusing what XLA would otherwise split into a reduce and
a multiply pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y.astype(o_ref.dtype) * g_ref[...])


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jax.Array, gain: jax.Array, *, eps: float = 1e-5,
            block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x: (..., d); gain: (d,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    n = 1
    for s in x.shape[:-1]:
        n *= s
    x2 = x.reshape(n, d)
    block_rows = min(block_rows, n)
    if n % block_rows:
        block_rows = 1
    grid = (n // block_rows,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, gain)
    return out.reshape(orig_shape)
