"""Optimizer parity with ``torch.optim.SGD(lr, momentum=0.9, weight_decay=1e-4)``
(reference ``distributed.py:63``) and MultiStepLR (``:64``)."""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from tpu_dist.train.optim import SGD, multistep_lr


def test_sgd_matches_torch_semantics():
    import torch

    w0 = np.random.default_rng(0).normal(size=(5, 3)).astype(np.float32)

    # torch ground truth
    tw = torch.nn.Parameter(torch.tensor(w0.copy()))
    opt = torch.optim.SGD([tw], lr=0.1, momentum=0.9, weight_decay=1e-4)
    grads = [np.random.default_rng(i + 1).normal(size=w0.shape).astype(np.float32) for i in range(4)]
    for g in grads:
        opt.zero_grad()
        tw.grad = torch.tensor(g.copy())
        opt.step()

    # ours
    sgd = SGD(momentum=0.9, weight_decay=1e-4)
    p = {"w": jnp.array(w0)}
    b = sgd.init(p)
    for g in grads:
        p, b = sgd.update({"w": jnp.array(g)}, b, p, 0.1)

    np.testing.assert_allclose(np.asarray(p["w"]), tw.detach().numpy(), rtol=1e-5, atol=1e-6)


def test_multistep_lr_schedule():
    sched = multistep_lr(0.1, (60, 120, 160), 0.2)
    assert sched(0) == 0.1
    assert sched(59) == 0.1
    assert np.isclose(sched(60), 0.02)
    assert np.isclose(sched(119), 0.02)
    assert np.isclose(sched(120), 0.004)
    assert np.isclose(sched(160), 0.0008)
    assert np.isclose(sched(199), 0.0008)


def test_adamw_matches_optax():
    import optax

    from tpu_dist.train.optim import AdamW

    # decay_mask="all" matches optax.adamw's unmasked default exactly
    opt = AdamW(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01, decay_mask="all")
    ref = optax.adamw(
        learning_rate=0.02, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01
    )

    params = {
        "w": jnp.asarray(np.random.default_rng(0).normal(size=(4, 3)), jnp.float32),
        "b": jnp.zeros((3,), jnp.float32),
    }
    ours_p, ours_s = params, opt.init(params)
    ref_p, ref_s = params, ref.init(params)

    rng = np.random.default_rng(1)
    for _ in range(5):
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32), params
        )
        ours_p, ours_s = opt.update(grads, ours_s, ours_p, 0.02)
        updates, ref_s = ref.update(grads, ref_s, ref_p)
        ref_p = optax.apply_updates(ref_p, updates)

    for a, b in zip(
        jax.tree_util.tree_leaves(ours_p), jax.tree_util.tree_leaves(ref_p)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_adamw_auto_mask_matches_optax_masked():
    """Default decay_mask='auto' == optax.adamw with the standard
    rank>1 mask: biases/norm scales get no decay (ADVICE r2)."""
    import optax

    from tpu_dist.train.optim import AdamW

    opt = AdamW(weight_decay=0.05)
    mask = lambda params: jax.tree_util.tree_map(lambda p: p.ndim > 1, params)
    ref = optax.adamw(learning_rate=0.02, weight_decay=0.05, mask=mask)

    params = {
        "w": jnp.asarray(np.random.default_rng(0).normal(size=(4, 3)), jnp.float32),
        "b": jnp.ones((3,), jnp.float32),  # nonzero so decay would show
        "ln": {"scale": jnp.ones((4,), jnp.float32)},
    }
    ours_p, ours_s = params, opt.init(params)
    ref_p, ref_s = params, ref.init(params)
    rng = np.random.default_rng(1)
    for _ in range(5):
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32), params
        )
        ours_p, ours_s = opt.update(grads, ours_s, ours_p, 0.02)
        updates, ref_s = ref.update(grads, ref_s, ref_p)
        ref_p = optax.apply_updates(ref_p, updates)
    for a, b in zip(
        jax.tree_util.tree_leaves(ours_p), jax.tree_util.tree_leaves(ref_p)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_trainer_adamw_e2e_with_resume(tmp_path):
    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer, register_model
    from tests.helpers import tiny_resnet

    register_model("tiny_resnet_aw", lambda num_classes=10: tiny_resnet(num_classes))
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_aw", num_classes=10,
        batch_size=64, epochs=1, steps_per_epoch=2, log_every=10, lr=1e-3,
        eval_every=0, optimizer="adamw", ckpt_dir=str(tmp_path), save_every=1,
    )
    t = Trainer(cfg)
    out = t.fit(1)
    assert np.isfinite(out["loss"])
    t2 = Trainer(cfg.replace(resume=True, epochs=2))
    assert t2.start_epoch == 1
    # AdamW's count buffer survives the roundtrip
    assert int(np.asarray(t2.state.opt_state["count"])) == int(
        np.asarray(t.state.opt_state["count"])
    )


def test_fsdp_adamw_matches_plain(tmp_path):
    """AdamW under FSDP: mu/nu shard like params, count replicates; the
    trajectory matches the replicated engine."""
    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.parallel.fsdp import fsdp_specs, make_fsdp_train_step
    from tpu_dist.train.optim import AdamW
    from tpu_dist.train.state import TrainState
    from tpu_dist.train.step import make_train_step
    from tests.helpers import TinyMLP

    mesh = mesh_lib.data_parallel_mesh()
    model = TinyMLP(width=128, in_dim=16)
    opt = AdamW()
    params, st = model.init(jax.random.PRNGKey(2))
    specs = fsdp_specs(params, mesh, min_size=64)
    opt_state = opt.init(params)
    opt_specs = fsdp_specs(opt_state, mesh, min_size=64)

    plain = jax.device_put(
        TrainState.create(params, st, opt), mesh_lib.replicated(mesh)
    )
    fsdp = TrainState(
        params=mesh_lib.place_host_tree(mesh, params, specs),
        bn_state=mesh_lib.place_host_tree(mesh, st),
        opt_state=mesh_lib.place_host_tree(mesh, opt_state, opt_specs),
        step=mesh_lib.place_host_tree(mesh, jnp.zeros((), jnp.int32)),
    )
    mu_leaf = fsdp.opt_state["mu"]["l1"]["w"]
    assert any(s is not None for s in mu_leaf.sharding.spec), "mu not sharded"

    plain_step = make_train_step(model.apply, opt, mesh, sync_bn=False, donate=False)
    fsdp_step = make_fsdp_train_step(
        model.apply, opt, mesh, specs, opt_specs=opt_specs, donate=False
    )

    rng = np.random.default_rng(3)
    for _ in range(3):
        x = mesh_lib.shard_batch(mesh, rng.normal(size=(64, 4, 4, 1)).astype(np.float32))
        y = mesh_lib.shard_batch(mesh, rng.integers(0, 10, 64).astype(np.int32))
        plain, mp = plain_step(plain, x, y, 1e-3)
        fsdp, mf = fsdp_step(fsdp, x, y, 1e-3)

    np.testing.assert_allclose(float(mp["loss"]), float(mf["loss"]), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.params), jax.tree_util.tree_leaves(fsdp.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def _large_batch_trajectory(opt, steps=4, lr=0.1):
    """Shared deterministic trajectory for the LARS/LAMB golden pins: a
    2-D weight (adapted + decayed) and a 1-D bias (excluded, like
    AdamW's ``auto`` mask)."""
    w0 = np.random.default_rng(0).normal(size=(4, 3)).astype(np.float32)
    b0 = (np.ones(3) * 0.5).astype(np.float32)
    p = {"w": jnp.array(w0), "b": jnp.array(b0)}
    s = opt.init(p)
    for i in range(steps):
        g = {
            "w": jnp.array(np.random.default_rng(i + 1).normal(size=(4, 3)).astype(np.float32)),
            "b": jnp.array(np.random.default_rng(100 + i).normal(size=(3,)).astype(np.float32)),
        }
        p, s = opt.update(g, s, p, lr)
    return p, s


def test_lars_matches_numpy_reference():
    """4 steps against an independent numpy transcription of the paper's
    update: ``local = η‖p‖/(‖g‖+wd‖p‖)``, momentum on the decayed+scaled
    gradient, rank≤1 leaves plain SGD-momentum."""
    from tpu_dist.train.optim import LARS

    mu, wd, eta, eps = 0.9, 1e-4, 1e-3, 1e-9
    w = np.random.default_rng(0).normal(size=(4, 3)).astype(np.float32)
    b = (np.ones(3) * 0.5).astype(np.float32)
    bw = np.zeros_like(w)
    bb = np.zeros_like(b)
    for i in range(4):
        gw = np.random.default_rng(i + 1).normal(size=(4, 3)).astype(np.float32)
        gb = np.random.default_rng(100 + i).normal(size=(3,)).astype(np.float32)
        pn, gn = np.linalg.norm(w), np.linalg.norm(gw)
        local = eta * pn / (gn + wd * pn + eps) if pn > 0 and gn > 0 else 1.0
        bw = mu * bw + local * (gw + wd * w)
        w = w - 0.1 * bw
        bb = mu * bb + gb  # no adaptation, no decay on rank-1
        b = b - 0.1 * bb

    p, _ = _large_batch_trajectory(LARS())
    np.testing.assert_allclose(np.asarray(p["w"]), w, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(p["b"]), b, rtol=1e-5, atol=1e-6)


def test_lamb_matches_numpy_reference():
    """Bias-corrected Adam direction, decoupled decay folded into the
    update, then the ‖p‖/‖u‖ trust ratio — numpy-transcribed."""
    from tpu_dist.train.optim import LAMB

    b1, b2, eps, wd = 0.9, 0.999, 1e-6, 0.01
    w = np.random.default_rng(0).normal(size=(4, 3)).astype(np.float32)
    b = (np.ones(3) * 0.5).astype(np.float32)
    mw = np.zeros_like(w); vw = np.zeros_like(w)
    mb = np.zeros_like(b); vb = np.zeros_like(b)
    for i in range(4):
        gw = np.random.default_rng(i + 1).normal(size=(4, 3)).astype(np.float32)
        gb = np.random.default_rng(100 + i).normal(size=(3,)).astype(np.float32)
        t = i + 1
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        mw = b1 * mw + (1 - b1) * gw; vw = b2 * vw + (1 - b2) * gw**2
        mb = b1 * mb + (1 - b1) * gb; vb = b2 * vb + (1 - b2) * gb**2
        uw = (mw / bc1) / (np.sqrt(vw / bc2) + eps) + wd * w
        r = np.linalg.norm(w) / (np.linalg.norm(uw) + eps)
        w = w - 0.1 * r * uw
        ub = (mb / bc1) / (np.sqrt(vb / bc2) + eps)  # no decay, ratio 1
        b = b - 0.1 * ub

    p, _ = _large_batch_trajectory(LAMB())
    np.testing.assert_allclose(np.asarray(p["w"]), w, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(p["b"]), b, rtol=1e-4, atol=1e-5)


def test_lars_lamb_golden_trajectory_pins():
    """Hard numeric pins of the shared trajectory — a silent change to
    either update rule (new default, reordered decay, dropped bias
    correction) moves these and fails loudly."""
    from tpu_dist.train.optim import LAMB, LARS

    p, s = _large_batch_trajectory(LARS())
    assert float(jnp.sum(p["w"])) == pytest.approx(0.26377815, rel=1e-4)
    assert float(p["w"][0, 0]) == pytest.approx(0.12542857, rel=1e-4)
    assert float(jnp.sum(p["b"])) == pytest.approx(1.37308383, rel=1e-4)
    # momentum state mirrors the param tree (ckpt/state_specs contract)
    assert set(s) == {"w", "b"}

    p, s = _large_batch_trajectory(LAMB())
    assert float(jnp.sum(p["w"])) == pytest.approx(-1.01437378, rel=1e-4)
    assert float(p["w"][0, 0]) == pytest.approx(-0.18847042, rel=1e-4)
    assert float(jnp.sum(p["b"])) == pytest.approx(1.30420136, rel=1e-4)
    # state layout is AdamW's exactly — checkpoints interop
    assert set(s) == {"mu", "nu", "count"}
    assert int(np.asarray(s["count"])) == 4


def test_linear_scaling_rule_and_warmup():
    from tpu_dist.train.optim import linear_scaled_lr

    assert linear_scaled_lr(0.1, 256, 2048) == pytest.approx(0.8)
    assert linear_scaled_lr(0.1, 256, 256) == pytest.approx(0.1)
    with pytest.raises(ValueError):
        linear_scaled_lr(0.1, 0, 256)
    with pytest.raises(ValueError):
        linear_scaled_lr(0.1, 256, -1)

    # warmup ramps linearly to base_lr, then the milestones take over
    sched = multistep_lr(0.8, (10, 20), 0.1, warmup_epochs=5)
    assert sched(0) == pytest.approx(0.8 / 5)
    assert sched(3) == pytest.approx(0.8 * 4 / 5)
    assert sched(4) == pytest.approx(0.8)
    assert sched(9) == pytest.approx(0.8)
    assert sched(10) == pytest.approx(0.08)
    # warmup_epochs=0 stays the reference MultiStepLR (no ramp)
    assert multistep_lr(0.8, (10,), 0.1)(0) == pytest.approx(0.8)


def test_trainer_lars_e2e_and_refusals(tmp_path):
    """LARS end-to-end through the Trainer with the full large-batch
    recipe (linear scaling + warmup), plus the two config refusals: the
    fused SGD kernel and the ZeRO-1 flat layout both destroy the
    per-layer norms LARS needs."""
    import pytest

    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer

    cfg = TrainConfig(
        dataset="synthetic", model="vit_tiny", num_classes=10, batch_size=64,
        epochs=1, steps_per_epoch=2, log_every=10, lr=0.1, lr_base_batch=256,
        warmup_epochs=1, eval_every=0, optimizer="lars", sync_bn=False,
        synthetic_n=256,
    )
    out = Trainer(cfg).fit()
    assert np.isfinite(out["loss"])

    with pytest.raises(ValueError, match="fused"):
        Trainer(cfg.replace(optimizer="lars", fused_optimizer=True))
    with pytest.raises(ValueError, match="ZeRO-1"):
        Trainer(cfg.replace(optimizer="lamb", shard_weight_update=True))


def test_trainer_adamw_tp_e2e():
    """AdamW under tensor parallelism: {mu,nu,count} placed/spec'd via
    optimizer.state_specs, train + eval run (the pytree-mismatch trap)."""
    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer

    cfg = TrainConfig(
        dataset="synthetic", model="vit_tiny", num_classes=10, batch_size=16,
        epochs=1, steps_per_epoch=2, log_every=1, lr=1e-3, eval_every=1,
        tp=2, sync_bn=False, synthetic_n=160, optimizer="adamw",
    )
    out = Trainer(cfg).fit()
    assert np.isfinite(out["loss"])
    assert "val_top1" in out
