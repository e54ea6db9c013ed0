"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode), and
prefill attention through the flash kernel against the XLA chunk scan."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as flash_kernel
from repro.models import Init, init_model, unbox
from repro.models import attention
from repro.models.model import prefill_step

RNG = np.random.default_rng(0)


def arr(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(0, scale, shape), dtype)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("B,Hq,Hkv,S,d", [
    (2, 4, 2, 256, 64), (1, 8, 8, 128, 128), (2, 6, 2, 128, 32),
    (1, 4, 1, 512, 64),
])
@pytest.mark.parametrize("mask", ["causal", "window", "chunk", "full"])
def test_flash_attention_sweep(B, Hq, Hkv, S, d, mask):
    kw = {"causal": dict(causal=True),
          "window": dict(causal=True, window=64),
          "chunk": dict(causal=True, chunk=128),
          "full": dict(causal=False)}[mask]
    q, k, v = arr(B, Hq, S, d), arr(B, Hkv, S, d), arr(B, Hkv, S, d)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64, **kw)
    gold = ref.ref_flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                               **tol(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q, k, v = (arr(1, 4, 128, 64, dtype=dtype) for _ in range(3))
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    gold = ref.ref_flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(gold, np.float32), **tol(dtype))


@pytest.mark.parametrize("B,Hq,Hkv,C,d", [
    (2, 4, 2, 256, 64), (3, 8, 8, 128, 32), (1, 16, 2, 512, 128),
])
@pytest.mark.parametrize("mask", ["none", "window", "chunk"])
def test_decode_attention_sweep(B, Hq, Hkv, C, d, mask):
    kw = {"none": {}, "window": dict(window=64),
          "chunk": dict(chunk=128)}[mask]
    q, k, v = arr(B, Hq, d), arr(B, Hkv, C, d), arr(B, Hkv, C, d)
    pos = jnp.asarray(RNG.integers(1, 3 * C, B), jnp.int32)
    out = ops.decode_attention(q, k, v, pos, block_k=64, **kw)
    gold = ref.ref_decode_attention(q, k, v, pos, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                               **tol(jnp.float32))


def test_decode_attention_short_history():
    """pos < C: unwritten ring slots must be masked out."""
    B, Hq, Hkv, C, d = 2, 4, 2, 128, 64
    q, k, v = arr(B, Hq, d), arr(B, Hkv, C, d), arr(B, Hkv, C, d)
    pos = jnp.asarray([3, 17], jnp.int32)
    out = ops.decode_attention(q, k, v, pos, block_k=64)
    gold = ref.ref_decode_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                               **tol(jnp.float32))


@pytest.mark.parametrize("B,H,S,hd", [(2, 3, 128, 64), (1, 2, 64, 32)])
@pytest.mark.parametrize("chunk", [16, 64])
def test_wkv_sweep(B, H, S, hd, chunk):
    r, k, v = arr(B, H, S, hd), arr(B, H, S, hd), arr(B, H, S, hd)
    w = jnp.asarray(RNG.uniform(0.8, 0.999, (B, H, S, hd)), jnp.float32)
    u = arr(H, hd)
    y, s = ops.wkv(r, k, v, w, u, chunk=chunk)
    yg, sg = ref.ref_wkv(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yg),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sg),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape", [(8, 256), (2, 5, 128), (3, 7, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = arr(*shape, dtype=dtype)
    g = arr(shape[-1], dtype=dtype)
    out = ops.rmsnorm(x, g)
    gold = ref.ref_rmsnorm(x, g)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(gold, np.float32), **tol(dtype))


S_PREFILL = 1024
GRANITE = get_config("granite-3-2b").reduced()


def _prefill(cfg, params, tokens, true_lens):
    step = jax.jit(functools.partial(prefill_step, cfg, max_len=2 * S_PREFILL))
    return step(params, {"tokens": tokens}, true_lens=true_lens)


@pytest.mark.parametrize("shape", [
    GRANITE,                                                  # GQA
    get_config("phi3-mini-3.8b").reduced(),                   # MHA
    dataclasses.replace(GRANITE, sliding_window=300),
    dataclasses.replace(GRANITE, attn_chunk=256),
], ids=["gqa", "mha", "window", "chunk"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_through_the_kernel_matches_the_chunk_scan(
        monkeypatch, shape, dtype):
    """Right-padded rows (``true_lens``) prefilled through attend's kernel
    path give the chunk scan's K/V cache and last-token logits: in float32
    to its tolerance; in bfloat16 within twice the chunk scan's own widest
    gap to the float32 model."""
    cfg = dataclasses.replace(shape, dtype=dtype)
    params, _ = unbox(init_model(Init(jax.random.PRNGKey(1),
                                      dtype=cfg.jnp_dtype), cfg))
    true_lens = jnp.asarray([S_PREFILL - 24, 301], jnp.int32)
    tokens = jnp.asarray(RNG.integers(1, cfg.vocab_size, (2, S_PREFILL)),
                         jnp.int32)
    tokens = jnp.where(jnp.arange(S_PREFILL)[None] < true_lens[:, None],
                       tokens, 0)
    assert not attention.flash_prefill(cfg, S_PREFILL, return_kv=True)
    want = _prefill(cfg, params, tokens, true_lens)
    f32 = dataclasses.replace(shape, dtype="float32")
    exact = _prefill(f32, jax.tree.map(lambda a: a.astype(jnp.float32),
                                       params), tokens, true_lens)
    with monkeypatch.context() as m:    # a TPU seen; the kernel interpreted
        m.setattr(ops, "_interpret", lambda: False)
        m.setattr(ops, "flash_attention",
                  functools.partial(flash_kernel, interpret=True))
        assert attention.flash_prefill(cfg, S_PREFILL, return_kv=True)
        got = _prefill(cfg, params, tokens, true_lens)
    assert np.array_equal(got[0]["pos"], want[0]["pos"])
    for g, w, e in [(got[0][k], want[0][k], exact[0][k]) for k in "kv"] + [
            (got[1], want[1], exact[1])]:
        g, w, e = (np.asarray(a, np.float32) for a in (g, w, e))
        if dtype == "float32":
            np.testing.assert_allclose(g, w, **tol(jnp.float32))
        else:
            assert np.abs(g - e).max() <= 2 * np.abs(w - e).max()


@pytest.mark.parametrize("tpu,cfg,seq_len,kw,kernel", [
    (True, GRANITE, 2048, dict(return_kv=True), True),
    (True, GRANITE, 2048, dict(return_kv=False), False),       # training
    (True, GRANITE, 2048, dict(return_kv=True, cross=True), False),
    (True, GRANITE, 2048, dict(return_kv=True, causal=False), False),
    (True, GRANITE, 2000, dict(return_kv=True), False),        # S % 128
    (True, dataclasses.replace(GRANITE, head_dim=320), 2048,
     dict(return_kv=True), False),
    (True, get_config("rwkv6-7b").reduced(), 2048, dict(return_kv=True),
     False),                                                   # no attention
    (False, GRANITE, 2048, dict(return_kv=True), False),       # the CPU
], ids=["prefill", "train", "cross", "non-causal", "seq-len", "head-dim",
        "attention-free", "cpu"])
def test_flash_prefill_dispatch(monkeypatch, tpu, cfg, seq_len, kw, kernel):
    if tpu:
        monkeypatch.setattr(ops, "_interpret", lambda: False)
    assert attention.flash_prefill(cfg, seq_len, **kw) is kernel
