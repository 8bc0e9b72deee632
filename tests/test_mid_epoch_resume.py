"""Exact mid-epoch resume: interrupt at step k, resume, train-to-identical
parameters vs an uninterrupted run.

The reference has no checkpointing at all (SURVEY §5); its interrupt story is
"re-run the epoch". This framework's emergency snapshot stamps the completed
step count (``mid_epoch_step``) into the checkpoint meta, and ``--resume``
re-enters the SAME epoch at that batch. Exactness rests on two properties
tested here:

* the sampler's epoch-seeded permutation + the loader's per-batch RNG keying
  make batch b bit-identical whether or not batches 0..b-1 were produced in
  this process (``DataLoader.iter_from``),
* the snapshot pairs (state, steps_done) atomically, so the restored state
  is exactly the one after ``steps_done`` optimizer steps.
"""

import numpy as np
import pytest

from tpu_dist.ckpt import latest_checkpoint, read_meta
from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.config import TrainConfig
from tpu_dist.data.loader import DataLoader
from tpu_dist.data.sampler import DistributedSampler
from tpu_dist.train.trainer import Trainer, register_model
from tests.helpers import tiny_resnet

register_model("tiny_resnet_mer", lambda num_classes=10: tiny_resnet(num_classes))


def _cfg(**kw):
    base = dict(
        dataset="synthetic", model="tiny_resnet_mer", num_classes=10,
        batch_size=64, epochs=2, log_every=100, eval_every=0,
        save_every=100, synthetic_n=640,  # 10 batches/epoch
    )
    base.update(kw)
    return TrainConfig(**base)


def _params_equal(a, b):
    import jax

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_loader_iter_from_matches_full_tail():
    """iter_from(k) must reproduce the full iteration's batches k.. exactly,
    including the augmentation stream (per-batch RNG keying)."""
    rng = np.random.default_rng(0)
    images = rng.normal(size=(100, 8, 8, 3)).astype(np.float32)
    labels = rng.integers(0, 10, size=100).astype(np.int32)
    sampler = DistributedSampler(100, shuffle=True, seed=3)
    sampler.set_epoch(1)

    def noisy(imgs, g):
        return imgs + g.normal(size=imgs.shape).astype(np.float32)

    mesh = mesh_lib.device_mesh([1], ["data"], __import__("jax").devices()[:1])
    loader = DataLoader(images, labels, batch_size=20, sampler=sampler,
                        mesh=mesh, transform=noisy, batch_divisor=1)
    full = [(np.asarray(i), np.asarray(l)) for i, l in loader]
    tail = [(np.asarray(i), np.asarray(l)) for i, l in loader.iter_from(2)]
    assert len(full) == 5 and len(tail) == 3
    for (fi, fl), (ti, tl) in zip(full[2:], tail):
        np.testing.assert_array_equal(fi, ti)
        np.testing.assert_array_equal(fl, tl)


def test_interrupt_at_step_k_resume_matches_uninterrupted(tmp_path, monkeypatch):
    # A: the uninterrupted reference trajectory
    t_full = Trainer(_cfg())
    t_full.fit()
    want = t_full.state

    # B: same run, interrupted mid-epoch 1 before its 4th step dispatches
    cfg = _cfg(ckpt_dir=str(tmp_path))
    t = Trainer(cfg)
    calls = {"n": 0}
    orig_step = t.train_step

    def interrupting(state, images, labels, lr):
        calls["n"] += 1
        if calls["n"] == 14:  # epoch 0 = 10 calls; epoch 1 step idx 3
            raise KeyboardInterrupt
        return orig_step(state, images, labels, lr)

    monkeypatch.setattr(t, "train_step", interrupting)
    with pytest.raises(KeyboardInterrupt):
        t.fit()

    found = latest_checkpoint(str(tmp_path))
    assert found is not None
    path, epoch = found
    assert epoch == 1
    assert read_meta(path).get("mid_epoch_step") == 3

    # C: resume — must re-enter epoch 1 at step 3 and finish bit-identical
    t2 = Trainer(cfg.replace(resume=True))
    assert t2.start_epoch == 1
    assert t2._resume_step == 3
    t2.fit()
    assert int(t2.state.step) == int(want.step)
    _params_equal(t2.state.params, want.params)
    _params_equal(t2.state.bn_state, want.bn_state)
    _params_equal(t2.state.opt_state, want.opt_state)


def test_reinterrupt_before_first_resumed_step_keeps_exact_position(
    tmp_path, monkeypatch
):
    """Interrupt again immediately after a mid-epoch resume (before any new
    step): the emergency path must re-save the SAME position, not regress to
    a clean-epoch-boundary save of a state that already holds k extra steps."""
    cfg = _cfg(ckpt_dir=str(tmp_path))
    t = Trainer(cfg)
    calls = {"n": 0}
    orig_step = t.train_step

    def interrupting(state, images, labels, lr):
        calls["n"] += 1
        if calls["n"] == 14:
            raise KeyboardInterrupt
        return orig_step(state, images, labels, lr)

    monkeypatch.setattr(t, "train_step", interrupting)
    with pytest.raises(KeyboardInterrupt):
        t.fit()

    t2 = Trainer(cfg.replace(resume=True))

    def immediate(state, images, labels, lr):
        raise KeyboardInterrupt

    monkeypatch.setattr(t2, "train_step", immediate)
    with pytest.raises(KeyboardInterrupt):
        t2.fit()
    path, epoch = latest_checkpoint(str(tmp_path))
    assert epoch == 1
    assert read_meta(path).get("mid_epoch_step") == 3

    # same but the interrupt lands BEFORE train_epoch even starts (the fit
    # preamble window) — the atomic _progress position must still re-save
    # the exact restore point, not misfile the k-step state as a clean
    # epoch boundary (reviewer finding r5)
    t3 = Trainer(cfg.replace(resume=True))

    def preamble_interrupt(epoch, start_step=0, start_examples=0):
        raise KeyboardInterrupt

    monkeypatch.setattr(t3, "train_epoch", preamble_interrupt)
    with pytest.raises(KeyboardInterrupt):
        t3.fit()
    path, epoch = latest_checkpoint(str(tmp_path))
    assert epoch == 1
    assert read_meta(path).get("mid_epoch_step") == 3


def test_mid_epoch_resume_sharded_ckpt(tmp_path, monkeypatch):
    """The exact-resume meta rides the sharded-checkpoint format too: the
    emergency snapshot goes through ShardedCheckpointer with the same
    mid_epoch_step stamp, and --resume re-enters at the exact batch."""
    from tpu_dist.ckpt import latest_sharded_checkpoint, read_sharded_meta

    t_full = Trainer(_cfg())
    t_full.fit()
    want = t_full.state

    cfg = _cfg(ckpt_dir=str(tmp_path), sharded_ckpt=True)
    t = Trainer(cfg)
    calls = {"n": 0}
    orig_step = t.train_step

    def interrupting(state, images, labels, lr):
        calls["n"] += 1
        if calls["n"] == 14:
            raise KeyboardInterrupt
        return orig_step(state, images, labels, lr)

    monkeypatch.setattr(t, "train_step", interrupting)
    with pytest.raises(KeyboardInterrupt):
        t.fit()

    found = latest_sharded_checkpoint(str(tmp_path))
    assert found is not None
    path, epoch = found
    assert epoch == 1
    assert read_sharded_meta(path).get("mid_epoch_step") == 3

    t2 = Trainer(cfg.replace(resume=True))
    assert t2.start_epoch == 1 and t2._resume_step == 3
    t2.fit()
    _params_equal(t2.state.params, want.params)
    _params_equal(t2.state.opt_state, want.opt_state)


def test_periodic_mid_epoch_snapshots_survive_kill(tmp_path):
    """--mid_epoch_save_every: periodic exact snapshots DURING the epoch,
    so a hard kill (no interrupt handler, no emergency save) loses at most
    N steps — resume re-enters at the last snapshot's batch and finishes
    bit-identical to an uninterrupted run."""
    from tpu_dist.ckpt import latest_checkpoint, read_meta

    t_full = Trainer(_cfg(epochs=1))
    t_full.fit()
    want = t_full.state

    cfg = _cfg(epochs=1, ckpt_dir=str(tmp_path), mid_epoch_save_every=4)
    t = Trainer(cfg)
    # simulate kill -9 after the epoch's work: run the raw epoch (which
    # writes snapshots at steps 4 and 8 of 10) and abandon the trainer
    # without fit()'s clean end-of-epoch save or any emergency path
    t.train_epoch(0)
    path, epoch = latest_checkpoint(str(tmp_path))
    assert epoch == 0
    assert read_meta(path).get("mid_epoch_step") == 8

    t2 = Trainer(cfg.replace(resume=True))
    assert t2.start_epoch == 0 and t2._resume_step == 8
    t2.fit()
    assert int(t2.state.step) == int(want.step)
    _params_equal(t2.state.params, want.params)
    _params_equal(t2.state.opt_state, want.opt_state)


def test_mid_epoch_save_every_rejected_with_fused_epoch():
    with pytest.raises(ValueError, match="no step boundary"):
        Trainer(_cfg(fused_epoch=True, mid_epoch_save_every=2,
                     batch_size=256, synthetic_n=512))


def test_mid_epoch_resume_refuses_batch_size_drift(tmp_path, monkeypatch):
    """The step offset only pins the data position under the same batch
    size/seed — a mismatched resume must refuse, not silently skip data."""
    cfg = _cfg(ckpt_dir=str(tmp_path))
    t = Trainer(cfg)
    calls = {"n": 0}
    orig_step = t.train_step

    def interrupting(state, images, labels, lr):
        calls["n"] += 1
        if calls["n"] == 14:
            raise KeyboardInterrupt
        return orig_step(state, images, labels, lr)

    monkeypatch.setattr(t, "train_step", interrupting)
    with pytest.raises(KeyboardInterrupt):
        t.fit()
    with pytest.raises(ValueError, match="wrong data position"):
        Trainer(cfg.replace(resume=True, batch_size=32))
    with pytest.raises(ValueError, match="wrong data position"):
        Trainer(cfg.replace(resume=True, seed=7))
