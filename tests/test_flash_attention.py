"""Pallas flash attention (ops/flash_attention.py) ≡ the XLA path.

Runs in interpret mode on the CPU mesh; checks forward AND custom-VJP
backward against ``full_attention`` over block-divisible, ragged (197),
and causal shapes, plus the dispatch/Trainer wiring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.nn.attention import (
    attention,
    full_attention,
    get_default_attention_impl,
    set_default_attention_impl,
)
from tpu_dist.ops.flash_attention import flash_attention


@pytest.mark.parametrize(
    "b,s,h,d,causal",
    [
        (2, 64, 2, 32, False),   # block-divisible
        (1, 197, 3, 64, False),  # ViT-B/16 length: padding + masking path
        (2, 40, 2, 16, True),    # causal, ragged
    ],
)
def test_flash_matches_xla_fwd_bwd(b, s, h, d, causal):
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32) for _ in range(3)
    )
    ref = full_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    ct = jnp.asarray(rng.normal(size=ref.shape), jnp.float32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * ct).sum()

    g_ref = jax.grad(loss(lambda *a: full_attention(*a, causal=causal)),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(
        loss(lambda *a: flash_attention(*a, causal=causal, block_q=32, block_k=32)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b_ in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)


def test_flash_bf16_dtype_and_accuracy():
    rng = np.random.default_rng(1)
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 64, 2, 32)), jnp.bfloat16) for _ in range(3)
    )
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_flash_block_size_invariance():
    rng = np.random.default_rng(2)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, 96, 2, 16)), jnp.float32) for _ in range(3)
    )
    a = flash_attention(q, k, v, block_q=16, block_k=48)
    b = flash_attention(q, k, v, block_q=96, block_k=96)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_attention_dispatch_impl():
    rng = np.random.default_rng(3)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, 32, 2, 16)), jnp.float32) for _ in range(3)
    )
    assert get_default_attention_impl() == "auto"
    try:
        set_default_attention_impl("flash")
        out = attention(q, k, v)
    finally:
        set_default_attention_impl("auto")
    ref = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    with pytest.raises(ValueError):
        set_default_attention_impl("nope")


def test_trainer_flash_attention_e2e():
    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer

    cfg = TrainConfig(
        dataset="synthetic", model="vit_tiny", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=2, log_every=10, eval_every=0,
        synthetic_n=64, sync_bn=False, flash_attention=True,
    )
    try:
        out = Trainer(cfg).train_epoch(0)
    finally:
        set_default_attention_impl("auto")
    assert np.isfinite(out["loss"])


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_bwd_matches_xla_bwd(causal):
    """The two backward formulations (tiled Pallas kernels vs blockwise
    lax.scan) are the same math — grads must agree to f32 round-off, on
    a ragged length exercising both padding paths."""
    rng = np.random.default_rng(3)
    b, s, h, d = 2, 100, 2, 32
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32) for _ in range(3)
    )
    ct = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)

    def grads(bwd):
        def loss(q, k, v):
            out = flash_attention(
                q, k, v, causal=causal, block_q=32, block_k=32, bwd=bwd
            )
            return jnp.vdot(out, ct)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for gp, gx, name in zip(grads("pallas"), grads("xla"), "qkv"):
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(gx), atol=3e-5,
            err_msg=f"d{name} mismatch between pallas and xla backward",
        )
