"""Ring attention ≡ full attention over a sequence-parallel mesh axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tpu_dist.comm.compat import shard_map
from jax.sharding import PartitionSpec as P

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.nn import attention as A


def _qkv(b=2, s=32, h=4, d=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), jnp.float32) for k in ks)


def test_full_attention_matches_manual_softmax():
    q, k, v = _qkv(s=8)
    out = A.full_attention(q, k, v)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8.0)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_ring_equals_full_8way():
    mesh = mesh_lib.device_mesh([8], ["seq"])
    q, k, v = _qkv(s=64)

    ring = jax.jit(
        shard_map(
            lambda q, k, v: A.ring_attention(q, k, v, "seq"),
            mesh=mesh,
            in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
            out_specs=P(None, "seq"),
            check_vma=False,
        )
    )
    out = np.asarray(ring(q, k, v))
    ref = np.asarray(A.full_attention(q, k, v))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_ring_causal_equals_full_causal():
    mesh = mesh_lib.device_mesh([4], ["seq"], jax.devices()[:4])
    q, k, v = _qkv(s=32, seed=3)

    ring = jax.jit(
        shard_map(
            lambda q, k, v: A.ring_attention(q, k, v, "seq", causal=True),
            mesh=mesh,
            in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"),
            check_vma=False,
        )
    )
    out = np.asarray(ring(q, k, v))
    ref = np.asarray(A.full_attention(q, k, v, causal=True))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_ring_attention_grads_flow():
    mesh = mesh_lib.device_mesh([4], ["seq"], jax.devices()[:4])
    q, k, v = _qkv(s=16, seed=1)

    def loss_sharded(q, k, v):
        def f(q, k, v):
            o = A.ring_attention(q, k, v, "seq")
            return jax.lax.psum(jnp.sum(o ** 2), "seq")

        return shard_map(
            f, mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(),
            check_vma=False,
        )(q, k, v)

    def loss_full(q, k, v):
        return jnp.sum(A.full_attention(q, k, v) ** 2)

    g_ring = jax.grad(loss_sharded)(q, k, v)
    g_full = jax.grad(loss_full)(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full), rtol=1e-3, atol=1e-4)


def test_ulysses_equals_full_4way():
    mesh = mesh_lib.device_mesh([4], ["seq"], jax.devices()[:4])
    q, k, v = _qkv(s=32, h=4, seed=5)

    uly = jax.jit(
        shard_map(
            lambda q, k, v: A.ulysses_attention(q, k, v, "seq"),
            mesh=mesh,
            in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"),
            check_vma=False,
        )
    )
    out = np.asarray(uly(q, k, v))
    ref = np.asarray(A.full_attention(q, k, v))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_ulysses_causal_and_grads_match_full():
    mesh = mesh_lib.device_mesh([4], ["seq"], jax.devices()[:4])
    q, k, v = _qkv(s=16, h=4, seed=6)

    def loss_sharded(q, k, v):
        def f(q, k, v):
            o = A.ulysses_attention(q, k, v, "seq", causal=True)
            return jax.lax.psum(jnp.sum(o ** 2), "seq")

        return shard_map(
            f, mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(),
            check_vma=False,
        )(q, k, v)

    def loss_full(q, k, v):
        return jnp.sum(A.full_attention(q, k, v, causal=True) ** 2)

    np.testing.assert_allclose(float(loss_sharded(q, k, v)), float(loss_full(q, k, v)), rtol=1e-5)
    g_u = jax.grad(loss_sharded)(q, k, v)
    g_f = jax.grad(loss_full)(q, k, v)
    np.testing.assert_allclose(np.asarray(g_u), np.asarray(g_f), rtol=1e-3, atol=1e-4)


def test_ulysses_with_flash_impl():
    """flash × SP: the ulysses local call runs the Pallas kernel."""
    mesh = mesh_lib.device_mesh([4], ["seq"], jax.devices()[:4])
    q, k, v = _qkv(s=32, h=4, seed=7)

    uly_flash = jax.jit(
        shard_map(
            lambda q, k, v: A.ulysses_attention(q, k, v, "seq", impl="flash"),
            mesh=mesh,
            in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"),
            check_vma=False,
        )
    )
    out = np.asarray(uly_flash(q, k, v))
    ref = np.asarray(A.full_attention(q, k, v))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    import pytest

    mesh = mesh_lib.device_mesh([4], ["seq"], jax.devices()[:4])
    q, k, v = _qkv(s=32, h=3, seed=8)
    with pytest.raises(ValueError, match="heads"):
        jax.jit(
            shard_map(
                lambda q, k, v: A.ulysses_attention(q, k, v, "seq"),
                mesh=mesh,
                in_specs=(P(None, "seq"),) * 3,
                out_specs=P(None, "seq"),
                check_vma=False,
            )
        )(q, k, v)


def test_explicit_impl_overrides_process_default(monkeypatch):
    """ADVICE r2: the step closure pins attn_impl at build time; an explicit
    impl= must win over the process-global default at trace time."""
    from tpu_dist.nn.vit import vit_tiny

    model = vit_tiny(num_classes=4, image_size=16)
    params, state = model.init(jax.random.PRNGKey(0))
    x = jnp.zeros((2, 16, 16, 3), jnp.float32)

    calls = []
    import tpu_dist.ops.flash_attention as fa

    real = fa.flash_attention
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda *a, **k: (calls.append(1), real(*a, **k))[1],
    )

    # global default says flash; explicit xla must NOT hit the kernel
    A.set_default_attention_impl("flash")
    try:
        model.apply(params, state, x, attn_impl="xla")
        assert not calls
        # and explicit flash hits it even when the global says xla
        A.set_default_attention_impl("xla")
        model.apply(params, state, x, attn_impl="flash")
        assert calls
    finally:
        A.set_default_attention_impl("auto")


def test_trainer_snapshots_attn_impl():
    """Two Trainers with different flash settings: each step closure keeps
    its own impl (the global default no longer leaks across builds). With
    no flag the attention chooses by shape ("auto"); FSDP's GSPMD step, where
    a Pallas call cannot be partitioned, pins XLA."""
    from tests.helpers import tiny_resnet
    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer, register_model

    register_model("tiny_resnet", lambda num_classes=10: tiny_resnet(num_classes))
    common = dict(
        dataset="synthetic", model="vit_tiny", num_classes=10, batch_size=64,
        epochs=1, steps_per_epoch=2, synthetic_n=128, sync_bn=False,
    )
    t_xla = Trainer(TrainConfig(**common))
    t_flash = Trainer(TrainConfig(**common, flash_attention=True))
    assert t_xla._attn_model_kwargs() == {"attn_impl": "auto"}
    assert t_flash._attn_model_kwargs() == {"attn_impl": "flash"}
    assert Trainer._attn_impl(TrainConfig(**common, fsdp=True)) == "xla"
    # conv models don't take the kwarg at all
    t_conv = Trainer(TrainConfig(dataset="synthetic", model="tiny_resnet",
                                 num_classes=10, batch_size=64, epochs=1,
                                 steps_per_epoch=2, synthetic_n=128))
    assert t_conv._attn_model_kwargs() == {}


def _ring_flash_fn(mesh, causal, block=16):
    from tpu_dist.ops.flash_attention import ring_flash_attention

    return jax.jit(
        shard_map(
            lambda q, k, v: ring_flash_attention(
                q, k, v, "seq", causal=causal, block_q=block, block_k=block
            ),
            mesh=mesh,
            in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
            out_specs=P(None, "seq"),
            check_vma=False,
        )
    )


def _run_or_skip_submesh(fn, *args):
    """Some jaxlibs cannot lower pallas-interpret inside shard_map on a
    SUB-mesh (4 of 8 devices): XLA emits a PartitionId instruction it then
    refuses under SPMD. Full-mesh ring-flash tests cover the numerics; the
    sub-mesh variants skip on that exact signature instead of failing."""
    try:
        return fn(*args)
    except Exception as e:  # jaxlib.xla_extension.XlaRuntimeError
        if "PartitionId instruction is not supported" in str(e):
            pytest.skip("jaxlib cannot lower pallas-interpret on a sub-mesh")
        raise


def test_ring_flash_equals_full_4way():
    mesh = mesh_lib.device_mesh([4], ["seq"], jax.devices()[:4])
    q, k, v = _qkv(s=64, seed=5)
    out = np.asarray(_run_or_skip_submesh(_ring_flash_fn(mesh, causal=False), q, k, v))
    ref = np.asarray(A.full_attention(q, k, v))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_ring_flash_bf16_causal_8way_grads():
    """8-way ring, bf16, causal: seven of eight rotations per device hit a
    non-diagonal lax.switch branch (the masked branch dominates), the
    configuration the round-5 TPU capture session runs at S=16k. Forward
    and grads must match the single-device flash kernel within bf16
    rounding."""
    from tpu_dist.ops.flash_attention import flash_attention

    mesh = mesh_lib.device_mesh([8], ["seq"], jax.devices()[:8])
    q, k, v = (t.astype(jnp.bfloat16) for t in _qkv(s=128, seed=12))
    fn = _ring_flash_fn(mesh, causal=True)
    out = np.asarray(fn(q, k, v), dtype=np.float32)
    ref = np.asarray(
        flash_attention(q, k, v, causal=True, block_q=16, block_k=16),
        dtype=np.float32,
    )
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)

    ct = jax.random.normal(jax.random.PRNGKey(13), q.shape, jnp.bfloat16)

    def g(f):
        return jax.grad(
            lambda q, k, v: jnp.vdot(
                f(q, k, v).astype(jnp.float32), ct.astype(jnp.float32)
            ),
            argnums=(0, 1, 2),
        )(q, k, v)

    g_ring = g(fn)
    g_ref = g(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16))
    for got, want, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32),
            rtol=4e-2, atol=4e-2, err_msg=f"d{name} bf16 causal 8-way",
        )


def test_ring_flash_causal_equals_full_causal():
    mesh = mesh_lib.device_mesh([4], ["seq"], jax.devices()[:4])
    q, k, v = _qkv(s=64, seed=6)
    out = np.asarray(_ring_flash_fn(mesh, causal=True)(q, k, v))
    ref = np.asarray(A.full_attention(q, k, v, causal=True))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_ring_flash_grads_match_full():
    """The custom ring backward (rotating dK/dV accumulators + global
    (m,l) statistics through the Pallas kernels) must match autodiff
    through the gathered reference, causal and not."""
    mesh = mesh_lib.device_mesh([4], ["seq"], jax.devices()[:4])
    q, k, v = _qkv(s=64, seed=7)
    ct = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

    for causal in (False, True):
        fn = _ring_flash_fn(mesh, causal=causal)

        def ring_loss(q, k, v):
            return jnp.vdot(fn(q, k, v), ct)

        def ref_loss(q, k, v):
            return jnp.vdot(A.full_attention(q, k, v, causal=causal), ct)

        g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for got, want, name in zip(g_ring, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5,
                err_msg=f"d{name} (causal={causal})",
            )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_bf16_matches_single_device_flash(causal):
    """bf16 inputs (the TPU training dtype): per-rotation partials merge
    in f32 — the ring result must stay within ONE bf16 rounding of the
    single-device flash kernel, not accumulate a fresh quantization per
    rotation.  causal=True is the advertised long-context training combo;
    its backward hits the masked lax.switch branch, whose zero-grads must
    carry the same f32 dtype as the kernel branches (advisor r4 finding)."""
    from tpu_dist.ops.flash_attention import flash_attention

    mesh = mesh_lib.device_mesh([4], ["seq"], jax.devices()[:4])
    q, k, v = (t.astype(jnp.bfloat16) for t in _qkv(s=64, seed=8))
    fn = _ring_flash_fn(mesh, causal=causal)
    out = np.asarray(_run_or_skip_submesh(fn, q, k, v), dtype=np.float32)
    ref = np.asarray(
        flash_attention(q, k, v, causal=causal, block_q=16, block_k=16),
        dtype=np.float32,
    )
    # bf16 has ~2^-8 relative precision; one rounding of each is ~1.6e-2
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)

    # backward too: per-rotation grad partials accumulate in f32, so ring
    # grads also stay within one bf16 rounding of the single-device kernel
    ct = jax.random.normal(jax.random.PRNGKey(11), q.shape, jnp.bfloat16)

    def g(f):
        return jax.grad(
            lambda q, k, v: jnp.vdot(
                f(q, k, v).astype(jnp.float32), ct.astype(jnp.float32)
            ),
            argnums=(0, 1, 2),
        )(q, k, v)

    g_ring = g(fn)
    g_ref = g(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=16, block_k=16))
    for got, want, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32),
            rtol=4e-2, atol=4e-2, err_msg=f"d{name} bf16 causal={causal}",
        )
