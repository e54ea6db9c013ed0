"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in ``interpret=True`` mode —
the kernel body runs in Python for correctness validation; on TPU they
compile to Mosaic. ``interpret`` is selected from the backend automatically.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rms
from repro.kernels import rwkv_wkv as _wkv


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    chunk: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               chunk=chunk, block_q=block_q, block_k=block_k,
                               interpret=_interpret())


def decode_attention(q, k, v, pos, *, window: Optional[int] = None,
                     chunk: Optional[int] = None, block_k: int = 512):
    return _dec.decode_attention(q, k, v, pos, window=window, chunk=chunk,
                                 block_k=block_k, interpret=_interpret())


def wkv(r, k, v, w, u, *, chunk: int = 64):
    return _wkv.wkv(r, k, v, w, u, chunk=chunk, interpret=_interpret())


def rmsnorm(x, gain, *, eps: float = 1e-5, block_rows: int = 256):
    return _rms.rmsnorm(x, gain, eps=eps, block_rows=block_rows,
                        interpret=_interpret())
