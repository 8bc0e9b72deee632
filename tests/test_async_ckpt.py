"""AsyncCheckpointer (ckpt/checkpoint.py): background writes publish the
same bytes as the sync path, in order, with errors surfaced — never lost."""

import numpy as np
import pytest

from tpu_dist import ckpt as ckpt_lib
from tpu_dist.train.optim import SGD
from tpu_dist.train.state import TrainState
from tests.helpers import TinyMLP

import jax


def _state(seed=0):
    model = TinyMLP()
    params, st = model.init(jax.random.PRNGKey(seed))
    return TrainState.create(params, st, SGD())


def test_async_save_matches_sync(tmp_path):
    state = _state()
    sync_dir, async_dir = tmp_path / "sync", tmp_path / "async"
    ckpt_lib.save(str(sync_dir), state, 3, extra_meta={"pp": 1})

    ac = ckpt_lib.AsyncCheckpointer()
    path = ac.save(str(async_dir), state, 3, extra_meta={"pp": 1})
    ac.wait()

    with np.load(sync_dir / "ckpt_3.npz") as a, np.load(path) as b:
        assert set(a.files) == set(b.files)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k])
    assert ckpt_lib.read_meta(path)["epoch"] == 3
    assert ckpt_lib.read_meta(path)["pp"] == 1


def test_async_keep_last_prunes_in_order(tmp_path):
    state = _state()
    ac = ckpt_lib.AsyncCheckpointer()
    for e in range(4):
        ac.save(str(tmp_path), state, e, keep_last=2)
    ac.wait()
    found = ckpt_lib.latest_checkpoint(str(tmp_path))
    assert found is not None and found[1] == 3
    import os

    kept = sorted(n for n in os.listdir(tmp_path) if n.startswith("ckpt_"))
    assert kept == ["ckpt_2.npz", "ckpt_3.npz"]


def test_async_save_best_roundtrip(tmp_path):
    state = _state()
    ac = ckpt_lib.AsyncCheckpointer()
    ac.save_best(str(tmp_path), state, 5, 73.2)
    ac.wait()
    meta = ckpt_lib.read_meta(str(tmp_path / "ckpt_best.npz"))
    assert meta["epoch"] == 5 and abs(meta["metric"] - 73.2) < 1e-9
    restored = ckpt_lib.restore(str(tmp_path / "ckpt_best.npz"), _state(seed=1))
    for a, b in zip(
        jax.tree_util.tree_leaves(restored.params),
        jax.tree_util.tree_leaves(state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_error_surfaces_on_wait(tmp_path):
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("file in the way")
    ac = ckpt_lib.AsyncCheckpointer()
    ac.save(str(blocker), _state(), 0)  # writer thread will fail on makedirs
    with pytest.raises(Exception):
        ac.wait()
    ac.wait()  # error is consumed once; subsequent waits are clean


def test_trainer_async_ckpt_e2e(tmp_path):
    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer, register_model
    from tests.helpers import tiny_resnet

    register_model("tiny_resnet_ack", lambda num_classes=10: tiny_resnet(num_classes))
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_ack", num_classes=10,
        batch_size=64, epochs=1, steps_per_epoch=2, log_every=10,
        eval_every=1, save_every=1, async_ckpt=True, ckpt_dir=str(tmp_path),
    )
    t = Trainer(cfg)
    t.fit(1)
    # fit() waited: files are fully published, resumable immediately
    assert (tmp_path / "ckpt_0.npz").exists()
    assert (tmp_path / "ckpt_best.npz").exists()
    t2 = Trainer(cfg.replace(resume=True, epochs=2))
    assert t2.start_epoch == 1
    for a, b in zip(
        jax.tree_util.tree_leaves(t.state.params),
        jax.tree_util.tree_leaves(t2.state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)
