"""ViT family: shapes, training, sequence-parallel forward parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tpu_dist.comm.compat import shard_map
from jax.sharding import PartitionSpec as P

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.nn.vit import ViTDef, vit_b16, vit_tiny
from tpu_dist.train.optim import SGD
from tpu_dist.train.state import TrainState
from tpu_dist.train.step import make_train_step


def test_vit_b16_param_count():
    # ViT-B/16 published size ≈ 86.6M (ImageNet-1k head, no cls token here)
    p, _ = vit_b16().init(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(p))
    assert 85e6 < n < 88e6, n


def test_vit_s16_param_count():
    from tpu_dist.nn.vit import vit_s16

    p, _ = vit_s16().init(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(p))
    # ViT-S/16 published ≈ 22M (cls-token variant); mean-pool variant close
    assert 20e6 < n < 23e6, n


def test_vit_b16_accepts_smaller_images():
    # --model vit_b16 on CIFAR-sized input: uses the leading pos embeddings
    m = vit_b16(num_classes=10)
    p, s = m.init(jax.random.PRNGKey(0))
    logits, _ = m.apply(p, s, jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 3)))
    assert logits.shape == (1, 10)


def test_vit_rejects_oversized_images():
    import pytest

    m = vit_tiny(image_size=32)
    p, s = m.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="positional"):
        m.apply(p, s, jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64, 3)))


def test_vit_forward_shape():
    m = vit_tiny()
    p, s = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    logits, _ = m.apply(p, s, x)
    assert logits.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_vit_trains_in_dp_step():
    mesh = mesh_lib.data_parallel_mesh()
    m = vit_tiny()
    opt = SGD()
    p, s = m.init(jax.random.PRNGKey(0))
    state = jax.device_put(TrainState.create(p, s, opt), mesh_lib.replicated(mesh))
    step = make_train_step(m.apply, opt, mesh, sync_bn=False)

    rng = np.random.default_rng(0)
    x = mesh_lib.shard_batch(mesh, rng.normal(size=(32, 32, 32, 3)).astype(np.float32))
    y = mesh_lib.shard_batch(mesh, rng.integers(0, 10, 32).astype(np.int32))
    losses = []
    for _ in range(20):
        state, met = step(state, x, y, 0.05)
        losses.append(float(met["loss"]))
    assert losses[-1] < losses[0]


def test_vit_seq_parallel_matches_single_device():
    """Sequence-parallel ViT forward over a 4-way 'seq' axis ≡ full forward."""
    m = ViTDef(image_size=32, patch_size=4, dim=32, depth=2, heads=2, num_classes=5)
    p, s = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))

    ref, _ = m.apply(p, s, x)

    mesh = mesh_lib.device_mesh([4], ["seq"], jax.devices()[:4])
    tokens = m.patchify(x)  # [B, 64, patch_dim]

    def f(p, tokens):
        out, _ = m.apply(p, {}, None, tokens=tokens, seq_axis="seq")
        return out

    sp = jax.jit(
        shard_map(
            f, mesh=mesh,
            in_specs=(P(), P(None, "seq")),
            out_specs=P(),
            check_vma=False,
        )
    )
    out = sp(p, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)
