"""End-to-end pipeline-parallel training (DP×PP, staged ViT)."""

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


def test_dp_pp_training_matches_single_device():
    from jax.sharding import NamedSharding

    model = _model()
    opt = SGD()
    mesh2d = mesh_lib.device_mesh([2, 4], ["data", "pipe"])
    mesh1 = mesh_lib.device_mesh([1], ["data"], jax.devices()[:1])
    specs = model.pp_param_specs("pipe")

    params, s = model.init(jax.random.PRNGKey(0))
    st = TrainState.create(params, s, opt)
    place = lambda tree: jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh2d, spec)), tree, specs
    )
    s_pp = TrainState(
        params=place(st.params),
        bn_state=jax.device_put(st.bn_state, mesh_lib.replicated(mesh2d)),
        opt_state=place(st.opt_state),
        step=jax.device_put(st.step, mesh_lib.replicated(mesh2d)),
    )
    s_1 = jax.device_put(st, mesh_lib.replicated(mesh1))

    step_pp = make_train_step(
        model.apply, opt, mesh2d, sync_bn=False, donate=False,
        pp_axis="pipe", param_specs=specs,
    )
    step_1 = make_train_step(model.apply, opt, mesh1, sync_bn=False, donate=False)

    rng = np.random.default_rng(0)
    for _ in range(3):
        x = rng.normal(size=(8, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 5, 8).astype(np.int32)
        s_pp, m_pp = step_pp(
            s_pp, mesh_lib.shard_batch(mesh2d, x), mesh_lib.shard_batch(mesh2d, y), 0.05
        )
        s_1, m_1 = step_1(
            s_1, mesh_lib.shard_batch(mesh1, x), mesh_lib.shard_batch(mesh1, y), 0.05
        )

    np.testing.assert_allclose(float(m_pp["loss"]), float(m_1["loss"]), rtol=1e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_pp.params)),
        jax.tree_util.tree_leaves(jax.device_get(s_1.params)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_trainer_pp_e2e_with_eval_and_resume(tmp_path):
    cfg = TrainConfig(
        dataset="synthetic", model="vit_pp_tiny", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=2, log_every=1, lr=0.05, eval_every=1,
        pp=4, sync_bn=False, synthetic_n=160, ckpt_dir=str(tmp_path), save_every=1,
    )
    t = Trainer(cfg)
    assert t.n_data == 2 and t.n_devices == 8
    out = t.fit()
    assert np.isfinite(out["loss"]) and "val_top1" in out

    t2 = Trainer(cfg.replace(resume=True, epochs=2))
    assert t2.start_epoch == 1
    blk_w = t2.state.params["blocks"]["qkv"]["w"]
    assert len(blk_w.sharding.device_set) == 8  # stages restored sharded
    assert np.isfinite(t2.fit()["loss"])


def test_trainer_pp_microbatches_flag():
    cfg = TrainConfig(
        dataset="synthetic", model="vit_pp_tiny", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=1, log_every=1, lr=0.05, eval_every=0,
        pp=4, pp_microbatches=8, sync_bn=False, synthetic_n=160,
    )
    out = Trainer(cfg).train_epoch(0)
    assert np.isfinite(out["loss"])


def test_trainer_pp_rejects_bad_configs():
    import pytest

    with pytest.raises(ValueError, match="pipeline parallelism"):
        Trainer(TrainConfig(dataset="synthetic", model="resnet18", pp=4, synthetic_n=512))
    with pytest.raises(ValueError, match="not divisible by pp"):
        Trainer(TrainConfig(dataset="synthetic", model="vit_pp_tiny", pp=8,
                            batch_size=64, synthetic_n=512))
