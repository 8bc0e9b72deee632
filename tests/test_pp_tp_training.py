"""End-to-end PP×TP training (DP×PP×TP — the Megatron layout: tensor
parallelism inside each pipeline stage).

Beyond the reference's scope (SURVEY §2.3: no model parallelism anywhere).
Pins: (a) the combined layout trains to the same parameters as a single
device, (b) the stacked block leaves really shard over BOTH the pipe and
model axes, (c) the Trainer CLI path (--pp + --tp) wires it end to end.
"""

import jax
import numpy as np
import pytest

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.config import TrainConfig
from tpu_dist.nn.vit_pp import ViTPipelineDef
from tpu_dist.train.optim import SGD
from tpu_dist.train.state import TrainState
from tpu_dist.train.step import make_train_step
from tpu_dist.train.trainer import Trainer


def _model():
    return ViTPipelineDef(image_size=16, patch_size=4, dim=32, depth=4, heads=4,
                          num_classes=5)


def test_dp_pp_tp_training_matches_single_device():
    from jax.sharding import NamedSharding

    model = _model()
    opt = SGD()
    mesh3d = mesh_lib.device_mesh([2, 2, 2], ["data", "pipe", "model"])
    mesh1 = mesh_lib.device_mesh([1], ["data"], jax.devices()[:1])
    specs = model.pp_tp_param_specs("pipe", "model")

    params, s = model.init(jax.random.PRNGKey(0))
    st = TrainState.create(params, s, opt)
    place = lambda tree: jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh3d, spec)),
        tree, specs,
    )
    s_pt = TrainState(
        params=place(st.params),
        bn_state=jax.device_put(st.bn_state, mesh_lib.replicated(mesh3d)),
        opt_state=place(st.opt_state),
        step=jax.device_put(st.step, mesh_lib.replicated(mesh3d)),
    )
    s_1 = jax.device_put(st, mesh_lib.replicated(mesh1))

    # block leaves must live on all 8 devices, split over pipe AND model
    qkv_w = s_pt.params["blocks"]["qkv"]["w"]
    assert len(qkv_w.sharding.device_set) == 8
    assert qkv_w.sharding.shard_shape(qkv_w.shape) == (2, 32, 48)  # depth/2, d, 3d/2

    step_pt = make_train_step(
        model.apply, opt, mesh3d, sync_bn=False, donate=False,
        pp_axis="pipe", tp_axis="model", param_specs=specs,
    )
    step_1 = make_train_step(model.apply, opt, mesh1, sync_bn=False, donate=False)

    rng = np.random.default_rng(0)
    for _ in range(3):
        x = rng.normal(size=(8, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 5, 8).astype(np.int32)
        s_pt, m_pt = step_pt(
            s_pt, mesh_lib.shard_batch(mesh3d, x), mesh_lib.shard_batch(mesh3d, y), 0.05
        )
        s_1, m_1 = step_1(
            s_1, mesh_lib.shard_batch(mesh1, x), mesh_lib.shard_batch(mesh1, y), 0.05
        )

    np.testing.assert_allclose(float(m_pt["loss"]), float(m_1["loss"]), rtol=1e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_pt.params)),
        jax.tree_util.tree_leaves(jax.device_get(s_1.params)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_dp_pp_tp_with_grad_clip_matches_single_device():
    """Shard-aware global-norm clip under BOTH model axes (blocks leaves
    grouped by (pipe, model) in clip_grads — one psum over both)."""
    from jax.sharding import NamedSharding

    model = _model()
    opt = SGD()
    mesh3d = mesh_lib.device_mesh([2, 2, 2], ["data", "pipe", "model"])
    mesh1 = mesh_lib.device_mesh([1], ["data"], jax.devices()[:1])
    specs = model.pp_tp_param_specs("pipe", "model")
    params, s = model.init(jax.random.PRNGKey(1))
    st = TrainState.create(params, s, opt)
    place = lambda tree: jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh3d, spec)),
        tree, specs,
    )
    s_pt = TrainState(place(st.params),
                      jax.device_put(st.bn_state, mesh_lib.replicated(mesh3d)),
                      place(st.opt_state),
                      jax.device_put(st.step, mesh_lib.replicated(mesh3d)))
    s_1 = jax.device_put(st, mesh_lib.replicated(mesh1))
    # tight clip so the scale actually engages
    step_pt = make_train_step(model.apply, opt, mesh3d, sync_bn=False,
                              donate=False, pp_axis="pipe", tp_axis="model",
                              param_specs=specs, grad_clip_norm=0.1)
    step_1 = make_train_step(model.apply, opt, mesh1, sync_bn=False,
                             donate=False, grad_clip_norm=0.1)
    rng = np.random.default_rng(2)
    x = rng.normal(size=(8, 16, 16, 3)).astype(np.float32)
    y = rng.integers(0, 5, 8).astype(np.int32)
    s_pt, _ = step_pt(s_pt, mesh_lib.shard_batch(mesh3d, x),
                      mesh_lib.shard_batch(mesh3d, y), 0.05)
    s_1, _ = step_1(s_1, mesh_lib.shard_batch(mesh1, x),
                    mesh_lib.shard_batch(mesh1, y), 0.05)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_pt.params)),
        jax.tree_util.tree_leaves(jax.device_get(s_1.params)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_trainer_pp_tp_e2e_with_eval(tmp_path):
    cfg = TrainConfig(
        dataset="synthetic", model="vit_pp_tiny", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=2, log_every=1, lr=0.05, eval_every=1,
        pp=2, tp=2, sync_bn=False, synthetic_n=160, ckpt_dir=str(tmp_path),
        save_every=1,
    )
    t = Trainer(cfg)
    assert t.n_data == 2 and t.n_devices == 8
    assert tuple(t.mesh.axis_names) == ("data", "pipe", "model")
    out = t.fit()
    assert np.isfinite(out["loss"]) and "val_top1" in out

    t2 = Trainer(cfg.replace(resume=True, epochs=2))
    assert t2.start_epoch == 1
    blk_w = t2.state.params["blocks"]["qkv"]["w"]
    assert len(blk_w.sharding.device_set) == 8  # restored sharded over pipe×model
    assert np.isfinite(t2.fit()["loss"])


def test_interleaved_pp_tp_training_matches_single_device():
    """Interleave composes too: virtual stages (device-major chunk storage)
    × TP inside each chunk, on the same [data, pipe, model] mesh."""
    from jax.sharding import NamedSharding

    model = ViTPipelineDef(image_size=16, patch_size=4, dim=32, depth=8,
                           heads=4, num_classes=5, interleave=2, pp_stages=2)
    opt = SGD()
    mesh3d = mesh_lib.device_mesh([2, 2, 2], ["data", "pipe", "model"])
    mesh1 = mesh_lib.device_mesh([1], ["data"], jax.devices()[:1])
    specs = model.pp_tp_param_specs("pipe", "model")
    params, s = model.init(jax.random.PRNGKey(3))
    st = TrainState.create(params, s, opt)
    place = lambda tree: jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh3d, spec)),
        tree, specs,
    )
    s_pt = TrainState(place(st.params),
                      jax.device_put(st.bn_state, mesh_lib.replicated(mesh3d)),
                      place(st.opt_state),
                      jax.device_put(st.step, mesh_lib.replicated(mesh3d)))
    s_1 = jax.device_put(st, mesh_lib.replicated(mesh1))
    step_pt = make_train_step(model.apply, opt, mesh3d, sync_bn=False,
                              donate=False, pp_axis="pipe", tp_axis="model",
                              param_specs=specs,
                              model_kwargs={"n_microbatches": 2})
    step_1 = make_train_step(model.apply, opt, mesh1, sync_bn=False, donate=False)
    rng = np.random.default_rng(4)
    for _ in range(2):
        x = rng.normal(size=(8, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 5, 8).astype(np.int32)
        s_pt, m_pt = step_pt(s_pt, mesh_lib.shard_batch(mesh3d, x),
                             mesh_lib.shard_batch(mesh3d, y), 0.05)
        s_1, m_1 = step_1(s_1, mesh_lib.shard_batch(mesh1, x),
                          mesh_lib.shard_batch(mesh1, y), 0.05)
    np.testing.assert_allclose(float(m_pt["loss"]), float(m_1["loss"]), rtol=1e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_pt.params)),
        jax.tree_util.tree_leaves(jax.device_get(s_1.params)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_trainer_tp_only_on_pipeline_model():
    """--tp without --pp on a vit_pp_* model: the stacked-block storage
    trains under pure Megatron TP (reviewer finding r5: the tp capability
    check passes for vit_pp now that apply takes tp_axis, so the specs
    must exist too)."""
    cfg = TrainConfig(
        dataset="synthetic", model="vit_pp_tiny", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=2, log_every=1, lr=0.05, eval_every=0,
        tp=2, sync_bn=False, synthetic_n=160,
    )
    t = Trainer(cfg)
    qkv_w = t.state.params["blocks"]["qkv"]["w"]
    # vit_pp_tiny: depth 4 stacked (unsharded), dim 64, qkv out-dim
    # 3*64=192 split over tp=2
    assert qkv_w.shape == (4, 64, 192)
    assert qkv_w.sharding.shard_shape(qkv_w.shape) == (4, 64, 96)
    out = t.train_epoch(0)
    assert np.isfinite(out["loss"])


def test_trainer_rejects_unsupported_pp_combos():
    with pytest.raises(ValueError, match="may be combined"):
        Trainer(TrainConfig(dataset="synthetic", model="vit_pp_tiny",
                            pp=2, sp=2, batch_size=16, synthetic_n=160,
                            sync_bn=False))
    with pytest.raises(ValueError, match="may be combined"):
        Trainer(TrainConfig(dataset="synthetic", model="vit_moe_tiny",
                            ep=2, tp=2, batch_size=16, synthetic_n=160,
                            sync_bn=False))
