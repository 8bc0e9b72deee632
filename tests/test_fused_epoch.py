"""Device-resident fused-epoch runner (tpu_dist/train/epoch.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpu_dist.analysis.jaxpr_audit import _walk_eqns
from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.data import synthetic_cifar
from tpu_dist.train.epoch import make_fused_epoch, put_dataset_on_device, random_crop
from tpu_dist.train.optim import SGD
from tpu_dist.train.state import TrainState
from tests.helpers import TinyConvNet


def _setup(n=256, bpd=4):
    mesh = mesh_lib.data_parallel_mesh()
    imgs, lbls = synthetic_cifar(n, 10, image_size=8, seed=0)
    dx, dy = put_dataset_on_device(mesh, imgs, lbls)
    model = TinyConvNet()
    opt = SGD()
    params, bn = model.init(jax.random.PRNGKey(0))
    state = jax.device_put(TrainState.create(params, bn, opt), mesh_lib.replicated(mesh))
    runner = make_fused_epoch(
        model.apply, opt, mesh, batch_per_device=bpd, compute_dtype=jnp.float32
    )
    return mesh, dx, dy, state, runner


def test_fused_epoch_runs_all_steps_and_trains():
    mesh, dx, dy, state, runner = _setup(n=256, bpd=4)
    # 256 examples / 8 devices = 32 local; bpd 4 -> 8 steps/epoch
    s1, m1 = runner(state, dx, dy, 0.1, 0)
    assert int(s1.step) == 8
    losses = [float(m1["loss"])]
    s = s1
    for e in range(1, 6):
        s, m = runner(s, dx, dy, 0.1, e)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    assert int(s.step) == 48


def test_fused_epoch_deterministic_per_epoch_idx():
    """Two builds at a fixed seed: the same loss in each of two consecutive
    epochs, the same parameters after them."""
    runs = []
    for _ in range(2):
        _, dx, dy, state, runner = _setup()
        losses = []
        for e in range(2):
            state, m = runner(state, dx, dy, 0.1, e)
            losses.append(float(m["loss"]))
        runs.append((losses, state))
    (la, a), (lb, b) = runs
    assert la[0] != la[1], la
    np.testing.assert_allclose(la, lb, rtol=1e-6)
    for x, y in zip(jax.tree_util.tree_leaves(a.params), jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


def test_fused_epoch_reshuffles_between_epochs():
    _, dx, dy, state, runner = _setup()
    s1, m1 = runner(state, dx, dy, 0.0, 0)  # lr=0: params frozen
    s2, m2 = runner(s1, dx, dy, 0.0, 1)
    # with lr=0 the only difference between epochs is batch order/augment →
    # metrics differ unless shuffling is broken
    assert float(m1["loss"]) != float(m2["loss"])


def test_fused_epoch_grad_compression():
    """The fused path honors the shared grad-compression contract: bf16
    wire trains (finite, close to uncompressed), bad modes are refused at
    build time (same validation as make_train_step)."""
    mesh = mesh_lib.data_parallel_mesh()
    imgs, lbls = synthetic_cifar(256, 10, image_size=8, seed=0)
    dx, dy = put_dataset_on_device(mesh, imgs, lbls)
    model = TinyConvNet()
    opt = SGD()
    params, bn = model.init(jax.random.PRNGKey(0))
    # host copies: the runner donates its input state, and device_put can
    # alias rather than copy — a donated alias would poison the second use
    params = jax.tree_util.tree_map(np.asarray, params)
    bn = jax.tree_util.tree_map(np.asarray, bn)

    def fresh_state():
        return jax.device_put(
            TrainState.create(params, bn, opt), mesh_lib.replicated(mesh)
        )

    plain = make_fused_epoch(
        model.apply, opt, mesh, batch_per_device=4, compute_dtype=jnp.float32
    )
    comp = make_fused_epoch(
        model.apply, opt, mesh, batch_per_device=4, compute_dtype=jnp.float32,
        grad_compression="bf16",
    )
    s_p, m_p = plain(fresh_state(), dx, dy, 0.1, 0)
    s_c, m_c = comp(fresh_state(), dx, dy, 0.1, 0)
    assert np.isfinite(float(m_c["loss"]))
    for a, b in zip(
        jax.tree_util.tree_leaves(s_p.params), jax.tree_util.tree_leaves(s_c.params)
    ):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=3e-2, atol=3e-3)

    with pytest.raises(ValueError, match="grad_compression"):
        make_fused_epoch(
            model.apply, opt, mesh, batch_per_device=4, grad_compression="fp16"
        )


def _reference_crop(imgs_u8, offs, pad):
    """The plain formulation ``random_crop`` replaced (a gather once
    batched): pad, then one ``dynamic_slice`` per image."""
    _, h, w, c = imgs_u8.shape
    xp = jnp.pad(imgs_u8, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    return jax.vmap(
        lambda img, off: lax.dynamic_slice(img, (off[0], off[1], 0), (h, w, c))
    )(xp, offs)


@pytest.mark.parametrize("pad", (2, 4))
@pytest.mark.parametrize("channels", (1, 3))
@pytest.mark.parametrize("batch", (1, 7, 64))
@pytest.mark.parametrize("size", (8, 32))
def test_random_crop_bit_identical_to_reference(size, batch, channels, pad):
    k_img, k_off = jax.random.split(jax.random.PRNGKey(size * batch + channels + pad))
    imgs = jax.random.randint(k_img, (batch, size, size, channels), 0, 256).astype(jnp.uint8)
    offs = jax.random.randint(k_off, (batch, 2), 0, 2 * pad + 1)
    got = random_crop(imgs, offs, pad)
    assert got.dtype == jnp.uint8 and got.shape == imgs.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_reference_crop(imgs, offs, pad)))

    # every corner of the padded image, and the centre: the image itself
    first = jnp.broadcast_to(imgs[:1], (5,) + imgs.shape[1:])
    corners = jnp.array(
        [[0, 0], [0, 2 * pad], [2 * pad, 0], [2 * pad, 2 * pad], [pad, pad]], jnp.int32
    )
    got = np.asarray(random_crop(first, corners, pad))
    np.testing.assert_array_equal(got, np.asarray(_reference_crop(first, corners, pad)))
    np.testing.assert_array_equal(got[4], np.asarray(imgs[0]))


def test_random_crop_has_no_data_dependent_slice():
    """The per-image loop cannot come back unnoticed: nothing in the crop's
    jaxpr (nested jits included) is a gather, a scatter, a dynamic slice or
    a loop, the forms the v5e compiler serialises over the batch. The same
    walk does find them in the reference."""
    imgs, offs = jnp.zeros((16, 32, 32, 3), jnp.uint8), jnp.zeros((16, 2), jnp.int32)

    def banned(crop):
        jaxpr = jax.make_jaxpr(lambda i, o: crop(i, o, 4))(imgs, offs)
        prims = {eqn.primitive.name for eqn, _ in _walk_eqns(jaxpr.jaxpr)}
        loops = {"dynamic_slice", "dynamic_update_slice", "while", "scan"}
        return {p for p in prims if p in loops or "gather" in p or "scatter" in p}

    assert banned(_reference_crop) == {"gather"}
    assert not banned(random_crop)
