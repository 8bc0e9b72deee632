"""Snapshot-then-write sharded checkpointing (--sharded_ckpt +
--async_ckpt, ckpt/checkpoint.py::AsyncShardedCheckpointer).

TD120 pins the composition's two invariants: the traced train step is
byte-identical whether or not a background writer is armed, and an
async-written checkpoint restores bit-exact to a synchronous sharded
save of the same state. The fault probes (EIO mid-background, SIGKILL
during the write, SIGTERM mid-run) must all be CAUGHT — a probe that
comes back clean means the detector is dead.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from tpu_dist.ckpt import checkpoint as ckpt_lib
from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.config import TrainConfig
from tpu_dist.resilience import faults, preemption
from tpu_dist.resilience.preemption import PREEMPTION_EXIT_CODE
from tpu_dist.train.state import TrainState
from tpu_dist.train.trainer import Trainer, register_model
from tests.helpers import TinyConvNet, tiny_resnet
from tests.test_sharded_ckpt import _fsdp_like_state

register_model("tiny_resnet_asc", lambda num_classes=10: tiny_resnet(num_classes))


@pytest.fixture(autouse=True)
def _clean_fault_state():
    faults.clear()
    preemption.clear()
    prev = ckpt_lib.set_io_retries(0)
    yield
    ckpt_lib.set_io_retries(prev)
    faults.clear()
    preemption.clear()


def _tree_equal(a, b):
    for x, y in zip(
        jax.tree_util.tree_leaves(a._asdict()),
        jax.tree_util.tree_leaves(b._asdict()),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _shard_crcs(ckpt_dir, stem):
    """{shard_file: {entry: crc32}} — the bit-identity comparison key
    (npz BYTES differ across saves via zip timestamps; the per-entry
    CRC32 stamps + restored-array equality are the format's identity)."""
    out = {}
    for nm in sorted(os.listdir(ckpt_dir)):
        if nm.startswith(f"{stem}.shard") and nm.endswith(".npz"):
            with np.load(os.path.join(ckpt_dir, nm)) as z:
                out[nm] = json.loads(bytes(z["__crc__"].tobytes()).decode())
    return out


# --------------------------------------------------------------------------
# TD120: restore bit-exact to the synchronous sharded format
# --------------------------------------------------------------------------


def test_async_save_bit_identical_to_sync(tmp_path):
    mesh = mesh_lib.data_parallel_mesh()
    state = _fsdp_like_state(mesh)
    sync_dir, async_dir = str(tmp_path / "sync"), str(tmp_path / "async")

    mpath_sync = ckpt_lib.save_sharded(sync_dir, state, 3, extra_meta={"k": 1})
    w = ckpt_lib.AsyncShardedCheckpointer()
    mpath_async = w.save(async_dir, state, 3, extra_meta={"k": 1})
    assert w.close(timeout=60.0)

    # same manifest name, same per-entry CRC32 stamps shard-for-shard
    assert os.path.basename(mpath_sync) == os.path.basename(mpath_async)
    assert _shard_crcs(sync_dir, "ckpt_3") == _shard_crcs(async_dir, "ckpt_3")
    ckpt_lib.verify_sharded(mpath_async, deep=True)
    assert ckpt_lib.read_sharded_meta(mpath_async)["k"] == 1

    # and the restored trees are bit-equal to each other AND the source
    r_sync = ckpt_lib.restore_sharded(mpath_sync, _fsdp_like_state(mesh))
    r_async = ckpt_lib.restore_sharded(mpath_async, _fsdp_like_state(mesh))
    _tree_equal(r_sync, r_async)
    _tree_equal(state, r_async)


def test_traced_step_byte_identical_with_writer_armed(tmp_path):
    """TD120's other half: arming the background writer must not change
    the traced step program — the snapshot is jax.device_get at the step
    boundary, never a traced op."""
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.step import make_train_step

    mesh = mesh_lib.data_parallel_mesh()
    model = TinyConvNet(num_classes=10, width=16)
    params, bn = model.init(jax.random.PRNGKey(0))
    opt = SGD(momentum=0.9)
    state = TrainState.create(params, bn, opt)
    step = make_train_step(model.apply, opt, mesh, sync_bn=False, donate=False)
    x = np.zeros((8, 8, 8, 3), np.float32)
    y = np.zeros((8,), np.int32)

    before = str(jax.make_jaxpr(step)(state, x, y, 0.1))
    w = ckpt_lib.AsyncShardedCheckpointer()
    w.save(str(tmp_path), _fsdp_like_state(mesh), 0)
    during = str(jax.make_jaxpr(step)(state, x, y, 0.1))
    assert w.close(timeout=60.0)
    after = str(jax.make_jaxpr(step)(state, x, y, 0.1))
    assert before == during == after


def test_async_blocks_only_for_snapshot(tmp_path, monkeypatch):
    """The submit path must return before the publish runs: slow the
    background write down and prove save() does not wait for it."""
    ev_started = []
    real_write = ckpt_lib._write_shard_file

    def slow_write(ckpt_dir, snap):
        ev_started.append(time.monotonic())
        time.sleep(0.5)
        return real_write(ckpt_dir, snap)

    monkeypatch.setattr(ckpt_lib, "_write_shard_file", slow_write)
    mesh = mesh_lib.data_parallel_mesh()
    state = _fsdp_like_state(mesh)
    w = ckpt_lib.AsyncShardedCheckpointer()
    t0 = time.monotonic()
    w.save(str(tmp_path), state, 0)
    blocked = time.monotonic() - t0
    assert blocked < 0.4, f"save() blocked {blocked:.2f}s on the publish"
    assert w.close(timeout=60.0)
    ckpt_lib.verify_sharded(
        os.path.join(str(tmp_path), "ckpt_0.manifest.json"), deep=True
    )


# --------------------------------------------------------------------------
# TD120: the EIO probe must be caught (dead detector = broken gate)
# --------------------------------------------------------------------------


def test_eio_mid_background_surfaces_at_drain(tmp_path):
    mesh = mesh_lib.data_parallel_mesh()
    state = _fsdp_like_state(mesh)
    w = ckpt_lib.AsyncShardedCheckpointer()
    w.save(str(tmp_path), state, 0)
    assert w.wait(timeout=60.0)  # epoch 0 committed clean

    faults.configure("ckpt_write@call=1")  # next shard write: EIO
    w.save(str(tmp_path), state, 1)
    with pytest.raises(OSError, match="fault-injected"):
        w.wait(timeout=60.0)
    faults.clear()
    w.close(timeout=60.0)

    # the failed epoch never committed; the ladder still points at 0
    found = ckpt_lib.latest_sharded_checkpoint(str(tmp_path))
    assert found is not None and found[1] == 0
    ckpt_lib.verify_sharded(found[0], deep=True)


def test_eio_retry_ladder_recovers_in_background(tmp_path):
    """--ckpt_io_retries still covers the background write: one injected
    EIO, two retries — the save must succeed and commit."""
    ckpt_lib.set_io_retries(2)
    faults.configure("ckpt_write@call=1")
    mesh = mesh_lib.data_parallel_mesh()
    state = _fsdp_like_state(mesh)
    w = ckpt_lib.AsyncShardedCheckpointer()
    w.save(str(tmp_path), state, 0)
    assert w.close(timeout=60.0)
    found = ckpt_lib.latest_sharded_checkpoint(str(tmp_path))
    assert found is not None and found[1] == 0
    ckpt_lib.verify_sharded(found[0], deep=True)


def test_bounded_drain_refuses_loudly(tmp_path, monkeypatch):
    """A drain that cannot finish in time returns False with in_flight
    still counted — the Trainer's _ckpt_close turns that into the
    counted ckpt.drain_abandoned loss, never a silent one."""
    real_write = ckpt_lib._write_shard_file

    def slow_write(ckpt_dir, snap):
        time.sleep(1.5)
        return real_write(ckpt_dir, snap)

    monkeypatch.setattr(ckpt_lib, "_write_shard_file", slow_write)
    mesh = mesh_lib.data_parallel_mesh()
    w = ckpt_lib.AsyncShardedCheckpointer()
    w.save(str(tmp_path), _fsdp_like_state(mesh), 0)
    assert w.close(timeout=0.05) is False
    assert w.in_flight == 1  # the abandoned write is COUNTED, not hidden


def test_same_stem_resave_drains_first(tmp_path):
    """Two saves to one stem (ckpt_best overwrite): the second submit
    must drain the first so the main-thread uncommit cannot race the
    background commit."""
    mesh = mesh_lib.data_parallel_mesh()
    state = _fsdp_like_state(mesh)
    w = ckpt_lib.AsyncShardedCheckpointer()
    w.save_best(str(tmp_path), state, 0, metric=1.0)
    w.save_best(str(tmp_path), state, 1, metric=2.0)
    assert w.close(timeout=60.0)
    mpath = os.path.join(str(tmp_path), "ckpt_best.manifest.json")
    ckpt_lib.verify_sharded(mpath, deep=True)
    assert ckpt_lib.read_sharded_meta(mpath)["metric"] == 2.0


# --------------------------------------------------------------------------
# Elastic: cross-extent restore of an async-written checkpoint
# --------------------------------------------------------------------------


def test_cross_extent_elastic_restore_of_async_written_ckpt(tmp_path):
    """A ZeRO-1 flat vector written by the BACKGROUND path at extent 8
    remaps onto a 4-device template exactly like a synchronous save —
    restore semantics are unchanged by who wrote the bytes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist.comm.quantize import padded_len
    from tpu_dist.elastic.remap import elastic_stamp, make_remapper

    def _mesh(n):
        return mesh_lib.device_mesh(
            [n], [mesh_lib.DATA_AXIS], jax.devices()[:n]
        )

    L = 26  # padded_len(26, 8)=32 vs padded_len(26, 4)=28: real reshape
    mesh8, mesh4 = _mesh(8), _mesh(4)
    w_arr = np.arange(24, dtype=np.float32).reshape(8, 3)
    b_arr = np.asarray([7.0, 9.0], np.float32)
    mom = np.zeros(padded_len(L, 8), np.float32)
    mom[:L] = np.arange(L, dtype=np.float32) * 1e-3
    st8 = TrainState(
        params={
            "b": jax.device_put(b_arr, NamedSharding(mesh8, P())),
            "w": jax.device_put(w_arr, NamedSharding(mesh8, P("data"))),
        },
        bn_state={},
        opt_state=jax.device_put(mom, NamedSharding(mesh8, P("data"))),
        step=jax.device_put(np.asarray(5, np.int32), NamedSharding(mesh8, P())),
    )
    writer = ckpt_lib.AsyncShardedCheckpointer()
    mpath = writer.save(
        str(tmp_path), st8, 0, extra_meta={"elastic": elastic_stamp(8, 1, L)}
    )
    assert writer.close(timeout=60.0)

    tmpl4 = TrainState(
        params={
            "b": jax.device_put(np.zeros_like(b_arr), NamedSharding(mesh4, P())),
            "w": jax.device_put(
                np.zeros_like(w_arr), NamedSharding(mesh4, P("data"))
            ),
        },
        bn_state={},
        opt_state=jax.device_put(
            np.zeros(padded_len(L, 4), np.float32),
            NamedSharding(mesh4, P("data")),
        ),
        step=jax.device_put(np.asarray(0, np.int32), NamedSharding(mesh4, P())),
    )
    rm = make_remapper(tmpl4, ckpt_lib.read_sharded_meta(mpath), 4)
    out = ckpt_lib.restore_sharded(mpath, tmpl4, remap=rm)
    np.testing.assert_array_equal(np.asarray(out.params["w"]), w_arr)
    got = np.asarray(out.opt_state)
    assert got.shape == (padded_len(L, 4),)
    np.testing.assert_array_equal(got[:L], mom[:L])
    assert int(np.asarray(out.step)) == 5


# --------------------------------------------------------------------------
# Crash probes: SIGKILL mid-write, SIGTERM mid-run (subprocess, slow)
# --------------------------------------------------------------------------

_SIGKILL_CHILD = r"""
import os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
from tpu_dist.ckpt import checkpoint as ckpt_lib
from tpu_dist.comm import mesh as mesh_lib
from tests.test_sharded_ckpt import _fsdp_like_state

ckpt_dir = sys.argv[1]
mesh = mesh_lib.data_parallel_mesh()
state = _fsdp_like_state(mesh)
ckpt_lib.save_sharded(ckpt_dir, state, 0)  # the committed floor

real = ckpt_lib._write_shard_file
def slow(d, snap):
    print("WRITE_STARTED", flush=True)  # parent kills -9 on this line
    time.sleep(30)
    return real(d, snap)
ckpt_lib._write_shard_file = slow

w = ckpt_lib.AsyncShardedCheckpointer()
w.save(ckpt_dir, state, 1)
w.wait()  # never returns: SIGKILL lands mid-write
"""


def test_sigkill_during_background_write_leaves_restorable_ladder(tmp_path):
    """Kill -9 while the background writer is mid-publish: whatever
    latest_sharded_checkpoint then returns must deep-verify and restore
    — the uncommit-first / manifest-last ordering means the torn epoch
    is invisible, not half-visible."""
    env = dict(os.environ, PYTHONPATH=os.path.dirname(os.path.dirname(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-c", _SIGKILL_CHILD, str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    try:
        deadline = time.monotonic() + 300
        for line in proc.stdout:
            if "WRITE_STARTED" in line:
                break
            if time.monotonic() > deadline:
                raise AssertionError("child never reached the write")
        proc.kill()  # SIGKILL: no cleanup, no drain
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGKILL

    found = ckpt_lib.latest_sharded_checkpoint(str(tmp_path))
    assert found is not None and found[1] == 0, found
    ckpt_lib.verify_sharded(found[0], deep=True)
    mesh = mesh_lib.data_parallel_mesh()
    restored = ckpt_lib.restore_sharded(found[0], _fsdp_like_state(mesh))
    _tree_equal(_fsdp_like_state(mesh), restored)


def test_cli_sigterm_drains_async_sharded_then_exit_75(tmp_path):
    """SIGTERM mid-run with the async+sharded composition: the trainer
    finishes the in-flight step, emergency-saves, DRAINS the background
    writer, and the CLI maps it to exit 75 — with a committed,
    deep-verifiable sharded checkpoint on disk."""
    from tpu_dist.cli.train import main

    with pytest.raises(SystemExit) as ei:
        main([
            "--dataset", "synthetic", "--model", "tiny_resnet_asc",
            "--num_classes", "10", "--batch_size", "64", "--epochs", "2",
            "--steps_per_epoch", "3", "--eval_every", "0", "--save_every",
            "1", "--synthetic_n", "256", "--seed", "0", "--log_every", "50",
            "--no_sync_bn", "--ckpt_dir", str(tmp_path),
            "--sharded_ckpt", "--async_ckpt",
            "--fault_plan", "sigterm@epoch=0:step=1",
        ])
    assert ei.value.code == PREEMPTION_EXIT_CODE
    found = ckpt_lib.latest_sharded_checkpoint(str(tmp_path))
    assert found is not None, sorted(os.listdir(tmp_path))
    ckpt_lib.verify_sharded(found[0], deep=True)


def test_trainer_async_sharded_resume_and_ckpt_accounting(tmp_path):
    """e2e: the once-refused --sharded_ckpt + --async_ckpt composition
    trains, commits every epoch, resumes from the manifest, and the
    goodput ledger accounts the (shrunken) blocking window in ckpt_s
    with the partition invariant intact."""
    log = str(tmp_path / "hist.jsonl")
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_asc", num_classes=10,
        batch_size=64, epochs=2, steps_per_epoch=2, eval_every=0,
        synthetic_n=256, sync_bn=False, sharded_ckpt=True, async_ckpt=True,
        ckpt_dir=str(tmp_path), save_every=1, log_every=10, log_file=log,
    )
    t = Trainer(cfg)
    t.fit()
    found = ckpt_lib.latest_sharded_checkpoint(str(tmp_path))
    assert found is not None and found[1] == 1
    ckpt_lib.verify_sharded(found[0], deep=True)

    # ckpt_s accounts the blocking window; the bucket partition stays
    # exact (buckets + unattributed == elapsed, the ledger invariant)
    from tpu_dist.obs import goodput as goodput_lib

    records = [json.loads(l) for l in open(log)]
    ledger = goodput_lib.run_ledger(records)
    assert ledger is not None and ledger["ckpt_s"] > 0.0
    parts = sum(ledger[f"{b}_s"] for b in goodput_lib.ALL_BUCKETS)
    assert abs(parts - ledger["elapsed_s"]) < 1e-3, ledger

    t2 = Trainer(cfg.replace(resume=True))
    assert t2.start_epoch == 2  # both epochs committed and visible

