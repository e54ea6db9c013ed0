import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Init, init_model, unbox
from repro.training import (
    AdamWConfig,
    TokenStream,
    adamw_update,
    init_opt_state,
    make_train_step,
    schedule,
)
from repro.training.grad_compress import (
    compress,
    compress_with_feedback,
    decompress,
)


def small_cfg():
    return get_config("dcache-agent-150m").reduced()


def test_schedule_warmup_and_decay():
    cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    lrs = [float(schedule(cfg, jnp.asarray(s))) for s in (1, 5, 10, 50, 100)]
    assert lrs[0] < lrs[1] < lrs[2]
    assert lrs[2] == pytest.approx(1e-3, rel=0.05)
    assert lrs[4] == pytest.approx(1e-4, rel=0.1)       # min_lr_frac


def test_adamw_moves_params_against_gradient():
    params = {"w": jnp.ones((4,), jnp.float32)}
    grads = {"w": jnp.ones((4,), jnp.float32)}
    opt = init_opt_state(params)
    cfg = AdamWConfig(lr=1e-2, weight_decay=0.0, warmup_steps=0,
                      total_steps=10)
    p2, opt2, m = adamw_update(cfg, params, grads, opt)
    assert (np.asarray(p2["w"]) < 1.0).all()
    assert int(opt2["step"]) == 1
    assert m["grad_norm"] > 0


@pytest.mark.slow
@pytest.mark.slow
def test_loss_decreases_over_training():
    cfg = small_cfg()
    params, _ = unbox(init_model(Init(jax.random.PRNGKey(0),
                                      dtype=cfg.jnp_dtype), cfg))
    step = jax.jit(make_train_step(
        cfg, AdamWConfig(lr=2e-3, warmup_steps=2, total_steps=40)))
    opt = init_opt_state(params)
    stream = TokenStream(cfg, batch=8, seq=32, seed=0)
    losses = []
    for _ in range(25):
        batch = {k: jnp.asarray(v) for k, v in stream.next_batch().items()}
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2


@pytest.mark.slow
@pytest.mark.slow
def test_grad_accum_matches_full_batch():
    cfg = dataclasses.replace(small_cfg(), dtype="float32")
    params, _ = unbox(init_model(Init(jax.random.PRNGKey(0),
                                      dtype=jnp.float32), cfg))
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10,
                          grad_clip=1e9)
    stream = TokenStream(cfg, batch=8, seq=16, seed=3)
    batch = {k: jnp.asarray(v) for k, v in stream.next_batch().items()}
    opt = init_opt_state(params)
    p1, _, _ = make_train_step(cfg, opt_cfg, accum_steps=1)(params, opt, batch)
    p2, _, _ = make_train_step(cfg, opt_cfg, accum_steps=2)(params, opt, batch)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_compress_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(0, 0.1, (1000,)), jnp.float32)
    codes, scale = compress(g)
    assert codes.dtype == jnp.int8
    approx = decompress(codes, scale, g.shape)
    err = np.abs(np.asarray(approx - g))
    assert err.max() <= float(np.abs(np.asarray(g)).max()) / 127 + 1e-6


def test_error_feedback_accumulates_lost_mass():
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(0, 0.1, (512,)), jnp.float32)
    res = jnp.zeros_like(g)
    total_applied = jnp.zeros_like(g)
    for _ in range(30):
        codes, scale, res = compress_with_feedback(g, res)
        total_applied = total_applied + decompress(codes, scale, g.shape)
    # after N steps, mean applied update ~= true gradient (unbiased)
    np.testing.assert_allclose(np.asarray(total_applied / 30),
                               np.asarray(g), atol=2e-3)


def test_compressed_psum_single_device():
    from repro.training.grad_compress import make_compressed_allreduce
    mesh = jax.make_mesh((1,), ("data",))
    g = jnp.linspace(-1, 1, 256)
    out = make_compressed_allreduce(mesh)(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=2e-2)
