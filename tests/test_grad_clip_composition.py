"""Grad-clip composes with every model-parallel axis (lifted walls).

Each test trains a few steps WITH an aggressively small clip norm (so the
clip is guaranteed active every step) under TP / EP / PP, and asserts the
resulting parameters are identical to a reference run without model
parallelism. The norm under model parallelism is computed shard-aware
(tpu_dist/train/step.py::clip_grads): sharded leaves contribute via one
psum over their model axes, replicated leaves locally.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.config import TrainConfig
from tpu_dist.nn import functional as F
from tpu_dist.train.optim import SGD
from tpu_dist.train.state import TrainState
from tpu_dist.train.step import make_train_step
from tpu_dist.train.trainer import Trainer

CLIP = 0.05  # far below typical init grad norms -> clip active every step


def _place(tree, mesh, specs):
    return jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)), tree, specs
    )


def _sharded_state(st, mesh, specs):
    return TrainState(
        params=_place(st.params, mesh, specs),
        bn_state=jax.device_put(st.bn_state, mesh_lib.replicated(mesh)),
        opt_state=_place(st.opt_state, mesh, specs),
        step=jax.device_put(st.step, mesh_lib.replicated(mesh)),
    )


def _assert_params_match(a_state, b_params):
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(a_state.params)),
        jax.tree_util.tree_leaves(jax.device_get(b_params)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_grad_clip_under_tp_matches_single_device():
    from tpu_dist.nn.vit import ViTDef

    model = ViTDef(image_size=32, patch_size=4, dim=32, depth=2, heads=4, num_classes=5)
    opt = SGD()
    mesh2d = mesh_lib.device_mesh([2, 4], ["data", "model"])
    mesh1 = mesh_lib.device_mesh([1], ["data"], jax.devices()[:1])
    specs = model.tp_param_specs("model")

    params, s = model.init(jax.random.PRNGKey(0))
    st = TrainState.create(params, s, opt)
    s_tp = _sharded_state(st, mesh2d, specs)
    s_1 = jax.device_put(st, mesh_lib.replicated(mesh1))

    step_tp = make_train_step(
        model.apply, opt, mesh2d, sync_bn=False, donate=False,
        tp_axis="model", param_specs=specs, grad_clip_norm=CLIP,
    )
    step_1 = make_train_step(
        model.apply, opt, mesh1, sync_bn=False, donate=False, grad_clip_norm=CLIP
    )
    step_1_noclip = make_train_step(
        model.apply, opt, mesh1, sync_bn=False, donate=False
    )
    s_noclip = jax.device_put(st, mesh_lib.replicated(mesh1))

    rng = np.random.default_rng(0)
    for _ in range(3):
        x = rng.normal(size=(8, 32, 32, 3)).astype(np.float32)
        y = rng.integers(0, 5, 8).astype(np.int32)
        s_tp, _ = step_tp(
            s_tp, mesh_lib.shard_batch(mesh2d, x), mesh_lib.shard_batch(mesh2d, y), 0.05
        )
        s_1, _ = step_1(
            s_1, mesh_lib.shard_batch(mesh1, x), mesh_lib.shard_batch(mesh1, y), 0.05
        )
        s_noclip, _ = step_1_noclip(
            s_noclip, mesh_lib.shard_batch(mesh1, x), mesh_lib.shard_batch(mesh1, y), 0.05
        )

    _assert_params_match(s_tp, s_1.params)
    # sanity: the clip actually changed the trajectory
    diffs = [
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(s_1.params)),
            jax.tree_util.tree_leaves(jax.device_get(s_noclip.params)),
        )
    ]
    assert max(diffs) > 1e-5, "clip norm never activated — test is vacuous"


def test_grad_clip_under_ep_matches_dense_reference():
    from tpu_dist.nn.vit_moe import ViTMoEDef

    model = ViTMoEDef(image_size=16, patch_size=4, dim=32, depth=1, heads=4,
                      n_experts=8, capacity_factor=8.0, num_classes=5)
    opt = SGD(momentum=0.9, weight_decay=0.0)
    mesh2d = mesh_lib.device_mesh([2, 4], ["data", "expert"])
    specs = model.ep_param_specs("expert")

    params, s = model.init(jax.random.PRNGKey(0))
    st = TrainState.create(params, s, opt)
    s_ep = _sharded_state(st, mesh2d, specs)
    step_ep = make_train_step(
        model.apply, opt, mesh2d, sync_bn=False, donate=False,
        ep_axis="expert", param_specs=specs, grad_clip_norm=CLIP,
    )

    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 16, 16, 3)).astype(np.float32)
    y = rng.integers(0, 5, 16).astype(np.int32)

    # host reference: mean of 8 shard losses, global-norm clip, plain SGD
    def ref_loss(p):
        tot = 0.0
        for i in range(8):
            logits, _ = model.apply(p, {}, jnp.asarray(x[i * 2: (i + 1) * 2]))
            tot = tot + F.cross_entropy(logits, jnp.asarray(y[i * 2: (i + 1) * 2]))
        return tot / 8

    def clip(g):
        sq = sum(jnp.sum(jnp.square(l)) for l in jax.tree_util.tree_leaves(g))
        scale = jnp.minimum(1.0, CLIP / jnp.maximum(jnp.sqrt(sq), 1e-12))
        return jax.tree_util.tree_map(lambda l: l * scale, g)

    ref_p, ref_b = params, opt.init(params)
    for _ in range(2):
        g = clip(jax.grad(ref_loss)(ref_p))
        ref_p, ref_b = opt.update(g, ref_b, ref_p, 0.05)

    xs = mesh_lib.shard_batch(mesh2d, x, ("data", "expert"))
    ys = mesh_lib.shard_batch(mesh2d, y, ("data", "expert"))
    for _ in range(2):
        s_ep, _ = step_ep(s_ep, xs, ys, 0.05)

    _assert_params_match(s_ep, ref_p)


def test_grad_clip_under_pp_matches_single_device():
    from tpu_dist.nn.vit_pp import ViTPipelineDef

    model = ViTPipelineDef(image_size=16, patch_size=4, dim=32, depth=4, heads=4,
                           num_classes=5)
    opt = SGD()
    mesh2d = mesh_lib.device_mesh([2, 4], ["data", "pipe"])
    mesh1 = mesh_lib.device_mesh([1], ["data"], jax.devices()[:1])
    specs = model.pp_param_specs("pipe")

    params, s = model.init(jax.random.PRNGKey(0))
    st = TrainState.create(params, s, opt)
    s_pp = _sharded_state(st, mesh2d, specs)
    s_1 = jax.device_put(st, mesh_lib.replicated(mesh1))

    step_pp = make_train_step(
        model.apply, opt, mesh2d, sync_bn=False, donate=False,
        pp_axis="pipe", param_specs=specs, grad_clip_norm=CLIP,
    )
    step_1 = make_train_step(
        model.apply, opt, mesh1, sync_bn=False, donate=False, grad_clip_norm=CLIP
    )

    rng = np.random.default_rng(0)
    for _ in range(3):
        x = rng.normal(size=(8, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 5, 8).astype(np.int32)
        s_pp, _ = step_pp(
            s_pp, mesh_lib.shard_batch(mesh2d, x), mesh_lib.shard_batch(mesh2d, y), 0.05
        )
        s_1, _ = step_1(
            s_1, mesh_lib.shard_batch(mesh1, x), mesh_lib.shard_batch(mesh1, y), 0.05
        )

    _assert_params_match(s_pp, s_1.params)


def test_trainer_accepts_clip_with_model_parallelism():
    """The trainer-level walls are lifted too: tp/ep/pp + grad_clip_norm
    train a finite step end to end."""
    for kw in (
        dict(model="vit_tiny", tp=4),
        dict(model="vit_moe_tiny", ep=4),
        dict(model="vit_pp_tiny", pp=4),
    ):
        cfg = TrainConfig(
            dataset="synthetic", num_classes=10, batch_size=32, epochs=1,
            steps_per_epoch=2, log_every=1, eval_every=0, lr=0.05,
            sync_bn=False, synthetic_n=320, grad_clip_norm=1.0, **kw,
        )
        out = Trainer(cfg).train_epoch(0)
        assert np.isfinite(out["loss"]), kw
