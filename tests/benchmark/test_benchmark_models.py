"""The analytic operation counts, the plain references and the comparison
that decides ``correct``, at sizes the CPU holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest, reference
from benchmarks.harness.data import make_dataset

RESNET18 = {"image_size": 32, "num_channels": 3, "num_classes": 100,
            "stage_blocks": [2, 2, 2, 2], "widths": [64, 128, 256, 512]}
VIT_B16 = {"hidden_size": 768, "num_hidden_layers": 12, "num_attention_heads": 12,
           "intermediate_size": 3072, "patch_size": 16, "image_size": 224,
           "num_channels": 3, "num_labels": 1000}


@pytest.fixture(scope="module")
def models(repo_root):
    return {name: manifest.load_module(repo_root, "models", name)
            for name in ("resnet_cifar", "vit")}


def test_resnet18_cifar_operations_by_hand(models):
    # stem 3x3x3x64 at 32x32; stage 1: four 3x3x64x64 convs at 32x32; stages
    # 2-4: 3x3 c/2->c, three 3x3 c->c and one 1x1 shortcut at half the side
    stem = 27 * 64 * 1024
    stage1 = 4 * 9 * 64 * 64 * 1024
    later = sum((9 * c // 2 * c + 3 * 9 * c * c + c // 2 * c) * side * side
                for c, side in ((128, 16), (256, 8), (512, 4)))
    want = stem + stage1 + later + 512 * 100
    got = models["resnet_cifar"].forward_macs_per_sample(RESNET18)
    assert got == want
    assert got == pytest.approx(0.56e9, rel=0.02)
    assert models["resnet_cifar"].train_flops_per_sample(RESNET18) == 6.0 * want


def test_vit_b16_operations_by_hand(models):
    vit = models["vit"]
    # the published model has 197 tokens (class token): 17.6 GMAC forward
    assert vit.forward_macs_per_sample(VIT_B16, tokens=197) == pytest.approx(17.6e9, rel=0.005)
    s, d, f = 196, 768, 3072
    layer = s * d * 3 * d + 2 * s * s * d + s * d * d + 2 * s * d * f
    want = s * 768 * d + 12 * layer + d * 1000
    assert vit.n_tokens(VIT_B16) == 196
    assert vit.forward_macs_per_sample(VIT_B16) == want
    assert vit.train_flops_per_sample(VIT_B16) == pytest.approx(104.8e9, rel=0.005)


def test_repo_configs_count_what_the_tests_count(repo_root, models):
    for cell, ref, arch in (("resnet18_cifar100.stream", "resnet_cifar", RESNET18),
                            ("vit_b16_imagenet.stream", "vit", VIT_B16)):
        got = manifest.load_cell(repo_root, cell).config
        assert got["reference"] == ref
        assert (models[ref].forward_macs_per_sample(got["arch"])
                == models[ref].forward_macs_per_sample(arch))


def _images(n, size, seed=0):
    x, y = make_dataset(n, size, 10, seed)
    return (x.astype(np.float32) / 255.0 - 0.5) / 0.25, y


def test_resnet_reference_agrees_with_the_program(models):
    from tpu_dist.nn.resnet import ResNetDef

    net = ResNetDef("basic", (1, 2, 1, 1), 10, widths=(8, 16, 16, 32))
    params, state = net.init(jax.random.PRNGKey(0))
    x, _ = _images(16, 16)
    arch = {"image_size": 16, "num_channels": 3, "num_classes": 10,
            "stage_blocks": [1, 2, 1, 1], "widths": [8, 16, 16, 32]}
    want, _ = net.apply(params, state, jnp.asarray(x), train=True)
    got = models["resnet_cifar"].logits(arch, params, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    assert n_params > 0 and models["resnet_cifar"].forward_macs_per_sample(arch) > 0


def test_vit_reference_agrees_with_the_program(models):
    from tpu_dist.nn.vit import ViTDef

    net = ViTDef(image_size=16, patch_size=4, dim=32, depth=2, heads=4, num_classes=10)
    params, state = net.init(jax.random.PRNGKey(1))
    x, _ = _images(8, 16)
    arch = {"hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
            "intermediate_size": 128, "patch_size": 4, "image_size": 16,
            "num_channels": 3, "num_labels": 10}
    want, _ = net.apply(params, state, jnp.asarray(x), train=True)
    got = models["vit"].logits(arch, params, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


VIT_TINY = {"hidden_size": 32, "num_hidden_layers": 1, "num_attention_heads": 2,
            "intermediate_size": 64, "patch_size": 4, "image_size": 8,
            "num_channels": 3, "num_labels": 10}


def _vit_tiny_setup():
    from tpu_dist.nn.vit import ViTDef

    net = ViTDef(image_size=8, patch_size=4, dim=32, depth=1, heads=2, num_classes=10)
    params, _ = net.init(jax.random.PRNGKey(2))
    x, y = _images(8, 8, seed=3)
    return net, params, x, y


def test_reference_in_chunks_equals_the_whole_batch(models):
    _, params, x, y = _vit_tiny_setup()
    dev = jax.devices()[0]
    whole = reference.reference_loss_and_grads(models["vit"], VIT_TINY, params, x, y, 0, dev)
    parts = reference.reference_loss_and_grads(models["vit"], VIT_TINY, params, x, y, 2, dev)
    assert parts[0] == pytest.approx(whole[0], rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(whole[1]), jax.tree_util.tree_leaves(parts[1])):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)
    with pytest.raises(ValueError, match="does not divide"):
        reference.reference_loss_and_grads(models["vit"], VIT_TINY, params, x, y, 3, dev)


def _program_update(optimizer, params, grads, lr):
    new, _ = optimizer.update(grads, optimizer.init(params), params, lr)
    return {"loss": 1.0, "lr": lr, "before": jax.device_get(params),
            "after": jax.device_get(new)}


@pytest.mark.parametrize("case", ["same", "half_precision_step", "dropped_term"])
def test_first_update_of_sgd_gives_the_gradient_back(models, case):
    from tpu_dist.train.optim import SGD

    _, params, x, y = _vit_tiny_setup()
    loss, grads = reference.reference_loss_and_grads(
        models["vit"], VIT_TINY, params, x, y, 0, jax.devices()[0])
    seen = grads
    if case == "half_precision_step":  # what a 3-bit mantissa does to every gradient
        seen = jax.tree_util.tree_map(
            lambda g: np.asarray(jnp.asarray(g).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                                 if np.abs(g).max() < 400 else g), grads)
    elif case == "dropped_term":  # the head's bias never learns
        seen = dict(grads, head=dict(grads["head"], b=np.zeros_like(grads["head"]["b"])))
    update = _program_update(SGD(0.9, 1e-4), params, seen, lr=0.1)
    update["loss"] = loss
    tc = {"optimizer": "sgd", "weight_decay": 1e-4}
    tol = {"loss_rel_tol": 1e-5, "grad_rel_l2_tol": 0.01, "leaf_cosine_min": 0.99}
    if case == "dropped_term":  # a small tensor: only the leaf-by-leaf cosine sees it
        tol = {"loss_rel_tol": 1e-5, "grad_rel_l2_tol": 0.5, "leaf_cosine_min": 0.5}
    verdict = reference.compare(update, loss, grads, tc, tol)
    assert verdict["ok"] == (case == "same"), verdict
    if case == "dropped_term":
        assert verdict["grad_rel_l2_err"] < 0.5 and verdict["worst_leaf_cosine"] < 0.5
    assert not reference.compare(dict(update, loss=loss * 1.01), loss, grads, tc, tol)["ok"]


@pytest.mark.parametrize("case", ["same", "flipped"])
def test_first_update_of_adamw_gives_the_sign_back(models, case):
    from tpu_dist.train.optim import AdamW

    _, params, x, y = _vit_tiny_setup()
    loss, grads = reference.reference_loss_and_grads(
        models["vit"], VIT_TINY, params, x, y, 0, jax.devices()[0])
    seen = grads
    if case == "flipped":  # one block's MLP learns backwards
        blk = dict(grads["blocks"][0], mlp1={k: -v for k, v in grads["blocks"][0]["mlp1"].items()})
        seen = dict(grads, blocks=[blk])
    update = _program_update(AdamW(weight_decay=0.05), params, seen, lr=1e-3)
    update["loss"] = loss
    tc = {"optimizer": "adamw", "weight_decay": 0.05}
    tol = {"loss_rel_tol": 1e-5, "sign_floor_rms": 0.5, "sign_agreement_min": 0.99}
    verdict = reference.compare(update, loss, grads, tc, tol)
    assert verdict["ok"] == (case == "same"), verdict
    assert 0.05 < verdict["sign_compared_share"] < 1.0


def test_dataset_follows_the_seed():
    a, la = make_dataset(40, 8, 10, seed=5, distinct=16)
    b, lb = make_dataset(40, 8, 10, seed=5, distinct=16)
    c, _ = make_dataset(40, 8, 10, seed=6, distinct=16)
    assert a.shape == (40, 8, 8, 3) and a.dtype == np.uint8 and a.flags["C_CONTIGUOUS"]
    assert (a == b).all() and (la == lb).all() and not (a == c).all()
    assert (a[:16] == a[16:32]).all() and not (a[0] == a[1]).all()
    assert la.dtype == np.int32 and 0 <= la.min() and la.max() < 10
