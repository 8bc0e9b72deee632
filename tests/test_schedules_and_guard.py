"""Cosine/warmup schedule and the NaN-guard failure detection."""

import numpy as np
import pytest

from tpu_dist.config import TrainConfig
from tpu_dist.train.optim import cosine_lr
from tpu_dist.train.trainer import Trainer, TrainingDivergedError, register_model
from tests.helpers import tiny_resnet

register_model("tiny_resnet_g", lambda num_classes=10: tiny_resnet(num_classes))


def test_cosine_schedule_shape():
    s = cosine_lr(1.0, total_epochs=100, warmup_epochs=10)
    assert np.isclose(s(0), 0.1)          # warmup ramp
    assert np.isclose(s(9), 1.0)
    assert np.isclose(s(10), 1.0)         # peak at warmup end
    assert s(55) < s(11)                  # decaying
    assert np.isclose(s(100), 0.0, atol=1e-8)
    s2 = cosine_lr(1.0, 100, warmup_epochs=0, min_lr=0.01)
    assert np.isclose(s2(0), 1.0)
    assert np.isclose(s2(100), 0.01)


def test_trainer_uses_cosine_when_configured():
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_g", num_classes=10,
        batch_size=64, epochs=10, lr=1.0, lr_schedule="cosine", warmup_epochs=2,
        eval_every=0,
    )
    t = Trainer(cfg)
    assert np.isclose(t.lr_schedule(0), 0.5)
    assert np.isclose(t.lr_schedule(1), 1.0)
    assert t.lr_schedule(9) < 0.1


def test_nan_guard_raises():
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_g", num_classes=10,
        batch_size=64, epochs=1, steps_per_epoch=3, log_every=1,
        lr=1e12, eval_every=0,  # guaranteed blow-up
    )
    t = Trainer(cfg)
    with pytest.raises(TrainingDivergedError, match="non-finite"):
        t.train_epoch(0)


def test_nan_guard_disabled_does_not_raise():
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_g", num_classes=10,
        batch_size=64, epochs=1, steps_per_epoch=2, log_every=1,
        lr=1e12, eval_every=0, nan_guard=False,
    )
    out = Trainer(cfg).train_epoch(0)
    assert not np.isfinite(out["loss"])


def test_nan_guard_catches_between_log_steps():
    # divergence after the last logged step must still raise at epoch end,
    # BEFORE fit() would checkpoint the poisoned state
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_g", num_classes=10,
        batch_size=64, epochs=1, steps_per_epoch=3, log_every=100,
        lr=1e12, eval_every=0,
    )
    with pytest.raises(TrainingDivergedError, match="end of epoch"):
        Trainer(cfg).train_epoch(0)


def test_nan_guard_covers_fused_epoch():
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_g", num_classes=10,
        batch_size=512, epochs=1, lr=1e12, eval_every=0, fused_epoch=True,
        synthetic_n=1024,  # 2 fused steps: keep the epoch-compile small
    )
    with pytest.raises(TrainingDivergedError, match="fused epoch"):
        Trainer(cfg).train_epoch(0)


def test_no_nan_guard_cli_flag():
    import argparse

    from tpu_dist.config import add_reference_flags, config_from_args

    p = add_reference_flags(argparse.ArgumentParser())
    cfg = config_from_args(p.parse_args(["--no_nan_guard"]))
    assert cfg.nan_guard is False
    assert config_from_args(p.parse_args([])).nan_guard is True


def test_auto_recover_reloads_and_backs_off(tmp_path):
    """--auto_recover: epoch 0 trains and checkpoints at lr=0.1, the
    milestone then multiplies LR by 1e13 and epoch 1 diverges; recovery
    reloads ckpt_0 and rescales the schedule (factor 1e-13 -> back to
    ~0.1), and the run completes with finite loss. The JSONL history
    records the recovery."""
    import json

    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_g", num_classes=10,
        batch_size=64, epochs=3, steps_per_epoch=3, log_every=1,
        lr=0.1, lr_milestones=(1,), lr_gamma=1e13, eval_every=0,
        ckpt_dir=str(tmp_path), save_every=1,
        auto_recover=1, recover_lr_factor=1e-13,
        log_file=str(tmp_path / "h.jsonl"),
    )
    t = Trainer(cfg)
    out = t.fit()
    assert np.isfinite(out["loss"]), out
    assert t._lr_scale == 1e-13
    events = [json.loads(l) for l in open(tmp_path / "h.jsonl")]
    assert any(e.get("kind") == "auto_recover" for e in events), events


def test_auto_recover_exhausted_reraises(tmp_path):
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_g", num_classes=10,
        batch_size=64, epochs=3, steps_per_epoch=3, log_every=1,
        lr=0.1, lr_milestones=(1,), lr_gamma=1e13, eval_every=0,
        ckpt_dir=str(tmp_path), save_every=1,
        auto_recover=2, recover_lr_factor=0.5,  # 5e11x is still a blow-up
    )
    with pytest.raises(TrainingDivergedError):
        Trainer(cfg).fit()


def test_auto_recover_without_ckpt_reraises(tmp_path):
    # divergence in epoch 0, nothing saved yet: nothing to recover FROM
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_g", num_classes=10,
        batch_size=64, epochs=1, steps_per_epoch=3, log_every=1,
        lr=1e12, eval_every=0, ckpt_dir=str(tmp_path), save_every=1,
        auto_recover=3,
    )
    with pytest.raises(TrainingDivergedError):
        Trainer(cfg).fit()


def test_auto_recover_scale_survives_resume(tmp_path):
    """The backoff is stamped into checkpoint meta: a --resume after a
    recovered run continues with the SCALED schedule instead of replaying
    the divergence (code-review r4)."""
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_g", num_classes=10,
        batch_size=64, epochs=3, steps_per_epoch=3, log_every=1,
        lr=0.1, lr_milestones=(1,), lr_gamma=1e13, eval_every=0,
        ckpt_dir=str(tmp_path), save_every=1,
        auto_recover=1, recover_lr_factor=1e-13,
    )
    t = Trainer(cfg)
    t.fit()
    assert t._lr_scale == 1e-13
    t2 = Trainer(cfg.replace(resume=True, epochs=4))
    assert t2._lr_scale == 1e-13  # picked up from ckpt meta, not reset


def test_emergency_save_refuses_poisoned_state(tmp_path):
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_g", num_classes=10,
        batch_size=64, epochs=1, steps_per_epoch=2, log_every=1,
        eval_every=0, ckpt_dir=str(tmp_path), save_every=1,
    )
    t = Trainer(cfg)
    t._last_epoch, t._in_epoch = 1, False
    t._state_poisoned = True  # the divergence-handling window
    t._emergency_save()
    import os

    assert os.listdir(tmp_path) == []  # nothing written
