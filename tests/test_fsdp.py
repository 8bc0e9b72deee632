"""FSDP (ZeRO-3 via GSPMD, parallel/fsdp.py) ≡ the plain data-parallel path.

Sharding annotations must change the schedule, never the math: every test
here drives the SAME batches through the explicit shard_map DP engine and
the GSPMD FSDP engine and asserts identical trajectories, while separately
asserting that the FSDP state really is sharded (the whole point)."""

import pytest
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.parallel.fsdp import (
    fsdp_specs,
    make_fsdp_eval_step,
    make_fsdp_train_step,
)
from tpu_dist.train.optim import SGD
from tpu_dist.train.state import TrainState
from tpu_dist.train.step import make_train_step
from tests.helpers import TinyConvNet, TinyMLP


def _mesh():
    return mesh_lib.data_parallel_mesh()


def test_fsdp_specs_rules():
    mesh = _mesh()  # 8 devices
    params = {
        "big_div": jnp.zeros((3, 3, 16, 64)),     # 64 % 8 == 0 -> sharded dim 3
        "big_lead": jnp.zeros((256, 5)),          # 256 % 8 == 0 -> sharded dim 0
        "big_nodiv": jnp.zeros((9, 121)),         # no dim divisible by 8
        "small": jnp.zeros((64,)),                # below min_size
        "scalar": jnp.zeros(()),
    }
    specs = fsdp_specs(params, mesh)
    assert specs["big_div"] == P(None, None, None, "data")
    assert specs["big_lead"] == P("data", None)
    assert specs["big_nodiv"] == P()
    assert specs["small"] == P()
    assert specs["scalar"] == P()


def _fsdp_state(mesh, params, bn, opt, specs):
    return TrainState(
        params=mesh_lib.place_host_tree(mesh, params, specs),
        bn_state=mesh_lib.place_host_tree(mesh, bn),
        opt_state=mesh_lib.place_host_tree(mesh, opt.init(params), specs),
        step=mesh_lib.place_host_tree(mesh, jnp.zeros((), jnp.int32)),
    )


def _assert_some_leaf_sharded(state):
    sharded = [
        l for l in jax.tree_util.tree_leaves(state.params)
        if any(s is not None for s in l.sharding.spec)
    ]
    assert sharded, "FSDP state has no sharded param leaf — specs degenerated"


def test_fsdp_matches_plain_dp_with_bn():
    """TinyConvNet has BatchNorm: checks GSPMD's global-batch statistics
    equal the shard_map SyncBN pmean path."""
    mesh = _mesh()
    model = TinyConvNet(width=16)
    opt = SGD()
    params, bn = model.init(jax.random.PRNGKey(0))
    specs = fsdp_specs(params, mesh, min_size=64)

    plain = jax.device_put(
        TrainState.create(params, bn, opt), mesh_lib.replicated(mesh)
    )
    fsdp = _fsdp_state(mesh, params, bn, opt, specs)
    _assert_some_leaf_sharded(fsdp)

    plain_step = make_train_step(model.apply, opt, mesh, donate=False, sync_bn=True)
    fsdp_step = make_fsdp_train_step(model.apply, opt, mesh, specs, donate=False)

    rng = np.random.default_rng(0)
    for _ in range(3):
        x = mesh_lib.shard_batch(mesh, rng.normal(size=(64, 8, 8, 3)).astype(np.float32))
        y = mesh_lib.shard_batch(mesh, rng.integers(0, 10, 64).astype(np.int32))
        plain, mp = plain_step(plain, x, y, 0.1)
        fsdp, mf = fsdp_step(fsdp, x, y, 0.1)

    for k in ("loss", "acc1", "acc5"):
        np.testing.assert_allclose(float(mp[k]), float(mf[k]), rtol=1e-5, atol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.params),
        jax.tree_util.tree_leaves(fsdp.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.bn_state),
        jax.tree_util.tree_leaves(fsdp.bn_state),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_fsdp_grad_accum_with_bn_matches_plain():
    """The hard case: BatchNorm + accumulation. Chunk membership must match
    the shard_map engine's per-device order or per-chunk global BN stats
    (and thus grads AND running stats) silently diverge."""
    mesh = _mesh()
    model = TinyConvNet(width=16)
    opt = SGD()
    params, bn = model.init(jax.random.PRNGKey(5))
    specs = fsdp_specs(params, mesh, min_size=64)

    plain = jax.device_put(
        TrainState.create(params, bn, opt), mesh_lib.replicated(mesh)
    )
    fsdp = _fsdp_state(mesh, params, bn, opt, specs)

    kw = dict(donate=False, grad_accum_steps=2)
    plain_step = make_train_step(model.apply, opt, mesh, sync_bn=True, **kw)
    fsdp_step = make_fsdp_train_step(model.apply, opt, mesh, specs, **kw)

    rng = np.random.default_rng(6)
    for _ in range(2):
        x = mesh_lib.shard_batch(mesh, rng.normal(size=(64, 8, 8, 3)).astype(np.float32))
        y = mesh_lib.shard_batch(mesh, rng.integers(0, 10, 64).astype(np.int32))
        plain, mp = plain_step(plain, x, y, 0.1)
        fsdp, mf = fsdp_step(fsdp, x, y, 0.1)

    for k in ("loss", "acc1", "acc5"):
        np.testing.assert_allclose(float(mp[k]), float(mf[k]), rtol=1e-5, atol=1e-5)
    for tree in ("params", "bn_state"):
        for a, b in zip(
            jax.tree_util.tree_leaves(getattr(plain, tree)),
            jax.tree_util.tree_leaves(getattr(fsdp, tree)),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )


def test_fsdp_grad_accum_and_clip_match_plain():
    """K=2 accumulation + global-norm clip, both engines, exact math model
    (TinyMLP is BN-free so trajectories are arithmetically identical)."""
    mesh = _mesh()
    model = TinyMLP(width=128, in_dim=16)
    opt = SGD()
    params, st = model.init(jax.random.PRNGKey(1))
    specs = fsdp_specs(params, mesh, min_size=64)

    plain = jax.device_put(
        TrainState.create(params, st, opt), mesh_lib.replicated(mesh)
    )
    fsdp = _fsdp_state(mesh, params, st, opt, specs)
    _assert_some_leaf_sharded(fsdp)

    kw = dict(donate=False, grad_accum_steps=2, grad_clip_norm=0.5)
    plain_step = make_train_step(model.apply, opt, mesh, sync_bn=False, **kw)
    fsdp_step = make_fsdp_train_step(model.apply, opt, mesh, specs, **kw)

    rng = np.random.default_rng(2)
    for _ in range(3):
        x = mesh_lib.shard_batch(mesh, rng.normal(size=(64, 4, 4, 1)).astype(np.float32))
        y = mesh_lib.shard_batch(mesh, rng.integers(0, 10, 64).astype(np.int32))
        plain, mp = plain_step(plain, x, y, 0.1)
        fsdp, mf = fsdp_step(fsdp, x, y, 0.1)

    np.testing.assert_allclose(float(mp["loss"]), float(mf["loss"]), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.params),
        jax.tree_util.tree_leaves(fsdp.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_fsdp_eval_step_sums_contract():
    """Masked global sums: padding rows contribute nothing, count is exact."""
    mesh = _mesh()
    model = TinyMLP(width=128, in_dim=16)
    params, st = model.init(jax.random.PRNGKey(3))
    opt = SGD()
    specs = fsdp_specs(params, mesh, min_size=64)
    state = _fsdp_state(mesh, params, st, opt, specs)

    eval_step = make_fsdp_eval_step(model.apply, mesh, specs)
    rng = np.random.default_rng(4)
    x = rng.normal(size=(16, 4, 4, 1)).astype(np.float32)
    y = rng.integers(0, 10, 16).astype(np.int32)
    mask = np.ones(16, np.float32)
    mask[-3:] = 0.0  # sampler padding
    sums = eval_step(
        state,
        mesh_lib.shard_batch(mesh, x),
        mesh_lib.shard_batch(mesh, y),
        mesh_lib.shard_batch(mesh, mask),
    )
    assert float(sums["count"]) == 13.0
    assert float(sums["top1"]) <= 13.0
    assert np.isfinite(float(sums["loss"]))


def test_trainer_fsdp_e2e_with_resume(tmp_path):
    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer, register_model
    from tests.helpers import tiny_resnet

    register_model("tiny_resnet_fsdp", lambda num_classes=10: tiny_resnet(num_classes))
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_fsdp", num_classes=10,
        batch_size=64, epochs=1, steps_per_epoch=3, log_every=10, lr=0.1,
        eval_every=1, fsdp=True, ckpt_dir=str(tmp_path), save_every=1,
    )
    t = Trainer(cfg)
    _assert_some_leaf_sharded(t.state)
    out = t.fit(1)
    assert np.isfinite(out["loss"])
    assert "val_top1" in out

    # resume restores into the sharded layout and continues
    t2 = Trainer(cfg.replace(resume=True, epochs=2))
    assert t2.start_epoch == 1
    _assert_some_leaf_sharded(t2.state)
    for a, b in zip(
        jax.tree_util.tree_leaves(t.state.params),
        jax.tree_util.tree_leaves(t2.state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)


def test_trainer_fsdp_flag_walls():
    import pytest

    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer

    base = dict(
        dataset="synthetic", num_classes=10, batch_size=16, epochs=1,
        synthetic_n=64, fsdp=True,
    )
    for bad in (
        dict(sp=2, model="vit_tiny"),  # sp/ep/pp stay refused; tp composes
        dict(shard_weight_update=True),
        dict(fused_epoch=True),
        dict(fused_optimizer=True),
        dict(debug_replica_check=True),
    ):
        with pytest.raises(ValueError):
            Trainer(TrainConfig(**base, **bad))


# -- FSDP x TP (VERDICT r2 #5) -----------------------------------------------


def _mesh_2d(tp=2):
    n = len(jax.devices())
    return mesh_lib.device_mesh(
        [n // tp, tp], [mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS]
    )


def test_compose_fsdp_specs_overlay():
    from tpu_dist.parallel.fsdp import compose_fsdp_specs

    mesh = _mesh_2d(tp=2)  # data=4, model=2
    params = {
        "qkv_w": jnp.zeros((64, 192)),   # model on dim1 -> data on dim0
        "proj_w": jnp.zeros((64, 64)),   # model on dim0 -> data on dim1
        "free": jnp.zeros((128, 33)),    # no model spec -> data on dim0
        "small_b": jnp.zeros((192,)),    # model on dim0, below min_size
        "tiny": jnp.zeros((8,)),
    }
    mspecs = {
        "qkv_w": P(None, "model"),
        "proj_w": P("model", None),
        "free": P(),
        "small_b": P("model"),
        "tiny": P(),
    }
    specs = compose_fsdp_specs(params, mesh, mspecs, min_size=1024)
    assert specs["qkv_w"] == P("data", "model")
    assert specs["proj_w"] == P("model", "data")
    assert specs["free"] == P("data")
    assert specs["small_b"] == P("model")  # model sharding preserved
    assert specs["tiny"] == P()


def test_fsdp_tp_matches_plain_dp():
    """FSDP x TP (GSPMD spec overlay) must be arithmetically identical to
    plain replicated DP: specs change the schedule, never the math."""
    from tpu_dist.nn.vit import vit_tiny
    from tpu_dist.parallel.fsdp import compose_fsdp_specs

    model = vit_tiny(num_classes=10, image_size=16)
    opt = SGD()
    params, st = model.init(jax.random.PRNGKey(7))

    mesh1 = _mesh()            # 8-way plain DP reference
    mesh2 = _mesh_2d(tp=2)     # data=4 x model=2
    specs = compose_fsdp_specs(
        params, mesh2, model.tp_param_specs(mesh_lib.MODEL_AXIS), min_size=256
    )
    # the composition must actually use BOTH axes somewhere
    flat = [tuple(s) for s in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P))]
    assert any("model" in f and "data" in f for f in flat), flat

    plain = jax.device_put(
        TrainState.create(params, st, opt), mesh_lib.replicated(mesh1)
    )
    fsdp = _fsdp_state(mesh2, params, st, opt, specs)
    _assert_some_leaf_sharded(fsdp)

    plain_step = make_train_step(model.apply, opt, mesh1, donate=False, sync_bn=False)
    fsdp_step = make_fsdp_train_step(model.apply, opt, mesh2, specs, donate=False)

    rng = np.random.default_rng(8)
    for _ in range(3):
        x = rng.normal(size=(32, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 10, 32).astype(np.int32)
        plain, mp = plain_step(
            plain, mesh_lib.shard_batch(mesh1, x), mesh_lib.shard_batch(mesh1, y), 0.1
        )
        fsdp, mf = fsdp_step(
            fsdp, mesh_lib.shard_batch(mesh2, x), mesh_lib.shard_batch(mesh2, y), 0.1
        )

    for k in ("loss", "acc1", "acc5"):
        np.testing.assert_allclose(float(mp[k]), float(mf[k]), rtol=1e-4, atol=1e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.params),
        jax.tree_util.tree_leaves(fsdp.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_trainer_fsdp_tp_e2e_adamw(tmp_path):
    """--fsdp --tp 2 trains, evals, checkpoints, resumes (AdamW state specs
    composed through optimizer.state_specs)."""
    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer

    cfg = TrainConfig(
        dataset="synthetic", model="vit_tiny", num_classes=10, batch_size=32,
        epochs=1, steps_per_epoch=3, log_every=10, lr=0.01, eval_every=1,
        fsdp=True, tp=2, sync_bn=False, optimizer="adamw",
        ckpt_dir=str(tmp_path), save_every=1, synthetic_n=128,
    )
    t = Trainer(cfg)
    # both mesh axes exist and params use the model axis somewhere
    assert dict(t.mesh.shape) == {"data": 4, "model": 2}
    flat = [
        tuple(l.sharding.spec)
        for l in jax.tree_util.tree_leaves(t.state.params)
    ]
    assert any("model" in f for f in flat), flat
    assert any("data" in f for f in flat), flat
    out = t.fit(1)
    assert np.isfinite(out["loss"])
    t2 = Trainer(cfg.replace(resume=True, epochs=2))
    assert t2.start_epoch == 1
    for a, b in zip(
        jax.tree_util.tree_leaves(t.state.params),
        jax.tree_util.tree_leaves(t2.state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)
