"""3-D DP×TP×SP training: Megatron sharding + ring attention on one mesh."""

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


def test_dp_tp_sp_training_matches_single_device():
    from jax.sharding import NamedSharding

    model = ViTDef(image_size=32, patch_size=4, dim=32, depth=2, heads=4, num_classes=5)
    opt = SGD()
    mesh3d = mesh_lib.device_mesh([2, 2, 2], ["data", "model", "seq"])
    mesh1 = mesh_lib.device_mesh([1], ["data"], jax.devices()[:1])
    specs = model.tp_param_specs("model")

    params, s = model.init(jax.random.PRNGKey(0))
    st = TrainState.create(params, s, opt)
    place = lambda tree: jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh3d, spec)), tree, specs
    )
    s_3d = TrainState(
        params=place(st.params),
        bn_state=jax.device_put(st.bn_state, mesh_lib.replicated(mesh3d)),
        opt_state=place(st.opt_state),
        step=jax.device_put(st.step, mesh_lib.replicated(mesh3d)),
    )
    s_1 = jax.device_put(st, mesh_lib.replicated(mesh1))

    step_3d = make_train_step(
        model.apply, opt, mesh3d, sync_bn=False, donate=False,
        tp_axis="model", seq_axis="seq", param_specs=specs,
    )
    step_1 = make_train_step(model.apply, opt, mesh1, sync_bn=False, donate=False)

    rng = np.random.default_rng(0)
    for _ in range(3):
        x = rng.normal(size=(8, 32, 32, 3)).astype(np.float32)
        y = rng.integers(0, 5, 8).astype(np.int32)
        s_3d, m3 = step_3d(
            s_3d, mesh_lib.shard_batch(mesh3d, x), mesh_lib.shard_batch(mesh3d, y), 0.05
        )
        s_1, m1 = step_1(
            s_1, mesh_lib.shard_batch(mesh1, x), mesh_lib.shard_batch(mesh1, y), 0.05
        )

    np.testing.assert_allclose(float(m3["loss"]), float(m1["loss"]), rtol=1e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_3d.params)),
        jax.tree_util.tree_leaves(jax.device_get(s_1.params)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_trainer_3d_e2e():
    cfg = TrainConfig(
        dataset="synthetic", model="vit_tiny", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=2, log_every=1, lr=0.05, eval_every=1,
        sp=2, tp=2, sync_bn=False, synthetic_n=160,
    )
    t = Trainer(cfg)
    assert t.n_data == 2 and t.n_devices == 8
    assert t.mesh.shape == {"data": 2, "model": 2, "seq": 2}
    out = t.fit()
    assert np.isfinite(out["loss"]) and "val_top1" in out


def test_trainer_still_rejects_other_combos():
    import pytest

    with pytest.raises(ValueError, match="only sp\\+tp"):
        Trainer(TrainConfig(dataset="synthetic", model="vit_moe_tiny", ep=2, pp=2,
                            synthetic_n=160))


def test_dp_tp_sp_ulysses_training_matches_single_device():
    """Same 3-D equivalence with the all_to_all (ulysses) SP strategy: each
    TP shard's 2 local heads redistribute over the 2-way seq axis."""
    from jax.sharding import NamedSharding

    model = ViTDef(image_size=32, patch_size=4, dim=32, depth=2, heads=4, num_classes=5)
    opt = SGD()
    mesh3d = mesh_lib.device_mesh([2, 2, 2], ["data", "model", "seq"])
    mesh1 = mesh_lib.device_mesh([1], ["data"], jax.devices()[:1])
    specs = model.tp_param_specs("model")

    params, s = model.init(jax.random.PRNGKey(0))
    st = TrainState.create(params, s, opt)
    place = lambda tree: jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh3d, spec)), tree, specs
    )
    s_3d = TrainState(
        params=place(st.params),
        bn_state=jax.device_put(st.bn_state, mesh_lib.replicated(mesh3d)),
        opt_state=place(st.opt_state),
        step=jax.device_put(st.step, mesh_lib.replicated(mesh3d)),
    )
    s_1 = jax.device_put(st, mesh_lib.replicated(mesh1))

    step_3d = make_train_step(
        model.apply, opt, mesh3d, sync_bn=False, donate=False,
        tp_axis="model", seq_axis="seq", param_specs=specs,
        model_kwargs={"sp_mode": "ulysses"},
    )
    step_1 = make_train_step(model.apply, opt, mesh1, sync_bn=False, donate=False)

    rng = np.random.default_rng(2)
    for _ in range(3):
        x = rng.normal(size=(8, 32, 32, 3)).astype(np.float32)
        y = rng.integers(0, 5, 8).astype(np.int32)
        s_3d, m3 = step_3d(
            s_3d, mesh_lib.shard_batch(mesh3d, x), mesh_lib.shard_batch(mesh3d, y), 0.05
        )
        s_1, m1 = step_1(
            s_1, mesh_lib.shard_batch(mesh1, x), mesh_lib.shard_batch(mesh1, y), 0.05
        )

    np.testing.assert_allclose(float(m3["loss"]), float(m1["loss"]), rtol=1e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_3d.params)),
        jax.tree_util.tree_leaves(jax.device_get(s_1.params)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)
