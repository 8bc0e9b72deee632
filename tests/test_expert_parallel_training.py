"""End-to-end expert-parallel training (DP×EP, MoE ViT)."""

import pytest
import jax
import numpy as np

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.config import TrainConfig
from tpu_dist.nn import functional as F
from tpu_dist.nn.vit_moe import ViTMoEDef
from tpu_dist.train.optim import SGD
from tpu_dist.train.state import TrainState
from tpu_dist.train.step import make_train_step
from tpu_dist.train.trainer import Trainer


def _model():
    # big capacity factor: no token drops → exact per-shard dense parity
    return ViTMoEDef(image_size=16, patch_size=4, dim=32, depth=1, heads=4,
                     n_experts=8, capacity_factor=8.0, num_classes=5)


def test_dp_ep_training_matches_per_shard_dense():
    """2×4 DP×EP step ≡ dense MoE computed shard-by-shard on one device
    (routing/capacity is per token shard in both)."""
    from jax.sharding import NamedSharding

    model = _model()
    opt = SGD(momentum=0.9, weight_decay=0.0)
    mesh2d = mesh_lib.device_mesh([2, 4], ["data", "expert"])
    specs = model.ep_param_specs("expert")

    params, s = model.init(jax.random.PRNGKey(0))
    st = TrainState.create(params, s, opt)
    place = lambda tree: jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh2d, spec)), tree, specs
    )
    s_ep = TrainState(
        params=place(st.params),
        bn_state=jax.device_put(st.bn_state, mesh_lib.replicated(mesh2d)),
        opt_state=place(st.opt_state),
        step=jax.device_put(st.step, mesh_lib.replicated(mesh2d)),
    )
    # aux coef 0: this test pins the dispatch/gradient math against a
    # train=False host reference; the aux objective has its own test
    # (test_parallel.py::test_moe_aux_loss_threads_through_train_step)
    step_ep = make_train_step(
        model.apply, opt, mesh2d, sync_bn=False, donate=False,
        ep_axis="expert", param_specs=specs, moe_aux_coef=0.0,
    )

    # host-side reference: same per-shard routing, gradient = mean of
    # 8 shard losses, plain SGD
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 16, 16, 3)).astype(np.float32)
    y = rng.integers(0, 5, 16).astype(np.int32)

    import jax.numpy as jnp

    def ref_loss(p):
        tot = 0.0
        for i in range(8):
            logits, _ = model.apply(p, {}, jnp.asarray(x[i * 2 : (i + 1) * 2]))
            tot = tot + F.cross_entropy(logits, jnp.asarray(y[i * 2 : (i + 1) * 2]))
        return tot / 8

    ref_p, ref_b = params, opt.init(params)
    for _ in range(2):
        g = jax.grad(ref_loss)(ref_p)
        ref_p, ref_b = opt.update(g, ref_b, ref_p, 0.05)

    xs = mesh_lib.shard_batch(mesh2d, x, ("data", "expert"))
    ys = mesh_lib.shard_batch(mesh2d, y, ("data", "expert"))
    for _ in range(2):
        s_ep, m = step_ep(s_ep, xs, ys, 0.05)

    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_ep.params)),
        jax.tree_util.tree_leaves(jax.device_get(ref_p)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_trainer_ep_e2e_with_eval_and_resume(tmp_path):
    cfg = TrainConfig(
        dataset="synthetic", model="vit_moe_tiny", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=2, log_every=1, lr=0.05, eval_every=1,
        ep=4, sync_bn=False, synthetic_n=160, ckpt_dir=str(tmp_path), save_every=1,
    )
    t = Trainer(cfg)
    assert t.n_devices == 8
    out = t.fit()
    assert np.isfinite(out["loss"]) and "val_top1" in out

    t2 = Trainer(cfg.replace(resume=True, epochs=2))
    assert t2.start_epoch == 1
    w_in = t2.state.params["blocks"][0]["moe"]["w_in"]
    assert len(w_in.sharding.device_set) == 8  # experts restored sharded
    assert np.isfinite(t2.fit()["loss"])


def test_trainer_ep_rejects_bad_configs():
    import pytest

    with pytest.raises(ValueError, match="expert parallelism"):
        Trainer(TrainConfig(dataset="synthetic", model="resnet18", ep=4, synthetic_n=512))
    with pytest.raises(ValueError, match="sp\\+tp"):
        Trainer(TrainConfig(dataset="synthetic", model="vit_moe_tiny", ep=2, tp=2,
                            synthetic_n=512))
