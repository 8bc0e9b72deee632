"""End-to-end tensor-parallel training (DP×TP, Megatron ViT) through
make_train_step and the Trainer."""

import jax
import numpy as np
import pytest

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.config import TrainConfig
from tpu_dist.nn.vit import ViTDef
from tpu_dist.train.optim import SGD
from tpu_dist.train.state import TrainState
from tpu_dist.train.step import make_train_step
from tpu_dist.train.trainer import Trainer


def _model():
    return ViTDef(image_size=32, patch_size=4, dim=32, depth=2, heads=4, num_classes=5)


def test_dp_tp_training_matches_single_device():
    from jax.sharding import NamedSharding

    model = _model()
    opt = SGD()
    mesh2d = mesh_lib.device_mesh([2, 4], ["data", "model"])
    mesh1 = mesh_lib.device_mesh([1], ["data"], jax.devices()[:1])
    specs = model.tp_param_specs("model")

    params, s = model.init(jax.random.PRNGKey(0))
    st = TrainState.create(params, s, opt)
    place = lambda tree: jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh2d, spec)), tree, specs
    )
    s_tp = TrainState(
        params=place(st.params),
        bn_state=jax.device_put(st.bn_state, mesh_lib.replicated(mesh2d)),
        opt_state=place(st.opt_state),
        step=jax.device_put(st.step, mesh_lib.replicated(mesh2d)),
    )
    s_1 = jax.device_put(st, mesh_lib.replicated(mesh1))

    step_tp = make_train_step(
        model.apply, opt, mesh2d, sync_bn=False, donate=False,
        tp_axis="model", param_specs=specs,
    )
    step_1 = make_train_step(model.apply, opt, mesh1, sync_bn=False, donate=False)

    rng = np.random.default_rng(0)
    for _ in range(3):
        x = rng.normal(size=(8, 32, 32, 3)).astype(np.float32)
        y = rng.integers(0, 5, 8).astype(np.int32)
        s_tp, m_tp = step_tp(
            s_tp, mesh_lib.shard_batch(mesh2d, x), mesh_lib.shard_batch(mesh2d, y), 0.05
        )
        s_1, m_1 = step_1(
            s_1, mesh_lib.shard_batch(mesh1, x), mesh_lib.shard_batch(mesh1, y), 0.05
        )

    np.testing.assert_allclose(float(m_tp["loss"]), float(m_1["loss"]), rtol=1e-4)
    # compare full (gathered) TP params with the single-device run
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_tp.params)),
        jax.tree_util.tree_leaves(jax.device_get(s_1.params)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_tp_forward_parity():
    """TP-sharded forward ≡ dense forward (eval-path insurance)."""
    import jax.numpy as jnp
    from tpu_dist.comm.compat import shard_map
    from jax.sharding import PartitionSpec as P

    model = _model()
    params, s = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3), jnp.float32)
    ref, _ = model.apply(params, s, x)

    mesh = mesh_lib.device_mesh([4], ["model"], jax.devices()[:4])
    specs = model.tp_param_specs("model")
    out = shard_map(
        lambda p, xl: model.apply(p, {}, xl, tp_axis="model")[0],
        mesh=mesh, in_specs=(specs, P()), out_specs=P(), check_vma=False,
    )(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_trainer_tp_e2e_with_eval_and_resume(tmp_path):
    cfg = TrainConfig(
        dataset="synthetic", model="vit_tiny", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=2, log_every=1, lr=0.05, eval_every=1,
        tp=4, sync_bn=False, synthetic_n=160, ckpt_dir=str(tmp_path), save_every=1,
    )
    t = Trainer(cfg)
    assert t.n_data == 2 and t.n_devices == 8
    out = t.fit()
    assert np.isfinite(out["loss"]) and "val_top1" in out

    t2 = Trainer(cfg.replace(resume=True, epochs=2))
    assert t2.start_epoch == 1
    # TP params restored SHARDED (each qkv leaf split over the model axis)
    qkv = t2.state.params["blocks"][0]["qkv"]["w"]
    assert len(qkv.sharding.device_set) == 8
    out2 = t2.fit()
    assert np.isfinite(out2["loss"])


def test_trainer_tp_rejects_bad_configs():
    import pytest

    with pytest.raises(ValueError, match="tensor parallelism"):
        Trainer(TrainConfig(dataset="synthetic", model="resnet18", tp=4, synthetic_n=512))
    with pytest.raises(ValueError, match="sp\\+tp"):  # sp+ep is NOT a valid combo
        Trainer(TrainConfig(dataset="synthetic", model="vit_tiny", sp=2, ep=2, synthetic_n=512))
    with pytest.raises(ValueError, match="incompatible"):
        # grad_clip_norm now composes with tp; ZeRO-1 remains structural
        Trainer(TrainConfig(
            dataset="synthetic", model="vit_tiny", tp=4, shard_weight_update=True,
            synthetic_n=512, batch_size=16,
        ))
