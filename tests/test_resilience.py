"""Resilience subsystem (docs/resilience.md): chaos-driven end-to-end tests.

Every fault here is injected through the deterministic ``--fault_plan``
machinery (tpu_dist/resilience/faults.py), so each scenario replays
bit-identically: SIGTERM mid-epoch resumes to the exact golden trajectory,
a corrupt newest checkpoint is quarantined with fallback to an older
epoch, transient write errors retry to a complete file, an injected NaN
drives the existing auto-recover path, and a dead loader producer raises
instead of hanging the epoch.
"""

import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import ckpt as ckpt_lib
from tpu_dist.ckpt import (
    CheckpointCorruptError,
    latest_checkpoint,
    read_meta,
    verify_npz,
)
from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.config import TrainConfig
from tpu_dist.data import DataLoader, DistributedSampler, synthetic_cifar
from tpu_dist.data.loader import LoaderProducerDiedError
from tpu_dist.resilience import FaultPlan, FaultPlanError, faults, preemption
from tpu_dist.resilience.preemption import PREEMPTION_EXIT_CODE, PreemptedError
from tpu_dist.resilience.retry import backoff_delays, retry_call
from tpu_dist.train.optim import SGD
from tpu_dist.train.state import TrainState
from tpu_dist.train.trainer import (
    Trainer,
    TrainingDivergedError,
    register_model,
)
from tests.helpers import TinyMLP

register_model(
    "tiny_mlp_rs", lambda num_classes=10: TinyMLP(num_classes, width=16, in_dim=3072)
)


@pytest.fixture(autouse=True)
def _clean_fault_state():
    """Every test starts and ends with no plan installed, no pending
    preemption flag, and the module-default retry count."""
    faults.clear()
    preemption.clear()
    prev = ckpt_lib.set_io_retries(0)
    yield
    faults.clear()
    preemption.clear()
    ckpt_lib.set_io_retries(prev)


def _cfg(ckpt_dir, **kw):
    base = dict(
        dataset="synthetic", model="tiny_mlp_rs", num_classes=10,
        batch_size=64, epochs=2, steps_per_epoch=3, log_every=50,
        eval_every=0, save_every=1, synthetic_n=256, seed=0,
        ckpt_dir=ckpt_dir, num_workers=1,
    )
    base.update(kw)
    return TrainConfig(**base)


def _ckpt_state(seed=0):
    k = jax.random.PRNGKey(seed)
    params = {"w": jax.random.normal(k, (4, 3)), "nested": {"b": jnp.ones(2)}}
    return TrainState.create(params, {}, SGD())


def _params_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


@pytest.fixture(scope="module")
def golden(tmp_path_factory):
    """One uninterrupted 2-epoch run — the bit-identity reference for every
    chaos scenario in this module."""
    d = tmp_path_factory.mktemp("golden")
    t = Trainer(_cfg(str(d)))
    last = t.fit()
    return jax.device_get(t.state.params), last


# -- fault-plan parsing ------------------------------------------------------


def test_fault_plan_parse_roundtrip():
    p = FaultPlan.parse(
        "ckpt_write@call=2:times=3;sigterm@epoch=1:step=5;"
        "ckpt_corrupt@epoch=0:mode=bitflip:seed=7;loader_stall@batch=4"
    )
    assert [c.site for c in p.clauses] == [
        "ckpt_write", "sigterm", "ckpt_corrupt", "loader_stall",
    ]
    assert p.clauses[0].params == {"call": 2, "times": 3}
    assert p.clauses[1].params == {"epoch": 1, "step": 5}
    assert p.clauses[2].params["seed"] == 7


@pytest.mark.parametrize(
    "bad",
    [
        "nosuchsite@x=1",            # unknown site
        "sigterm@",                  # missing required step
        "ckpt_write@call=abc",       # non-integer coordinate
        "ckpt_corrupt@epoch=0:mode=banana",  # bad corruption mode
        "sigterm@step=1:frac=0.5",   # key not allowed for the site
        "sigterm",                   # no trigger at all
        "  ;  ",                     # no clauses
    ],
)
def test_fault_plan_rejects_malformed_specs(bad):
    with pytest.raises(FaultPlanError):
        FaultPlan.parse(bad)


def test_fault_plan_env_fallback_and_clear(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "nan_loss@step=3")
    plan = faults.configure(None)
    assert plan is not None and plan.clauses[0].site == "nan_loss"
    monkeypatch.delenv(faults.ENV_VAR)
    assert faults.configure(None) is None  # no cfg + no env => cleared
    assert faults.active() is None


def test_clauses_are_one_shot_by_default():
    faults.install("nan_loss@step=2")
    assert faults.on_step(0, 1) == frozenset()
    assert faults.NAN_LOSS in faults.on_step(0, 2)
    assert faults.on_step(1, 2) == frozenset()  # disarmed after firing


# -- retry ladder ------------------------------------------------------------


def test_backoff_schedule_is_deterministic():
    assert backoff_delays(4, 0.05, 2.0) == (0.05, 0.1, 0.2, 0.4)
    assert backoff_delays(3, 1.0, 1.5) == (1.0, 1.5, 1.5)  # capped
    assert backoff_delays(0) == ()


def test_retry_call_succeeds_after_transients_and_reraises_on_exhaustion():
    calls, sleeps = {"n": 0}, []

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise OSError(5, "eio")
        return "ok"

    assert retry_call(flaky, retries=3, sleep=sleeps.append) == "ok"
    assert sleeps == [0.05, 0.1]  # the deterministic schedule, injectable

    def always():
        raise OSError(28, "enospc")

    with pytest.raises(OSError, match="enospc"):
        retry_call(always, retries=1, sleep=sleeps.append)
    # non-retryable types propagate immediately (no sleeps consumed)
    n0 = len(sleeps)

    def typeerr():
        raise TypeError("not transient")

    with pytest.raises(TypeError):
        retry_call(typeerr, retries=3, sleep=sleeps.append)
    assert len(sleeps) == n0  # propagated without sleeping


def test_transient_ckpt_write_failures_retry_to_a_complete_file(
    tmp_path, monkeypatch
):
    import tpu_dist.resilience.retry as retry_mod

    sleeps = []
    monkeypatch.setattr(retry_mod.time, "sleep", sleeps.append)
    ckpt_lib.set_io_retries(2)
    faults.install("ckpt_write@call=1:times=2")  # first two ATTEMPTS fail
    st = _ckpt_state()
    path = ckpt_lib.save(str(tmp_path), st, epoch=0)
    assert path is not None and os.path.exists(path)
    verify_npz(path)  # complete and CRC-clean after the retries
    assert sleeps == [0.05, 0.1]
    # restored bytes match the state that was saved
    rt = ckpt_lib.restore(path, _ckpt_state(seed=9))
    assert _params_equal(rt.params, st.params)


def test_ckpt_write_retry_exhaustion_raises_and_leaves_no_checkpoint(tmp_path):
    ckpt_lib.set_io_retries(1)
    faults.install("ckpt_write@call=1:times=5")
    with pytest.raises(OSError):
        ckpt_lib.save(
            str(tmp_path), _ckpt_state(), epoch=0,
        )
    assert latest_checkpoint(str(tmp_path)) is None  # nothing partial


# -- checkpoint integrity ----------------------------------------------------


def test_crc_stamps_written_and_verified(tmp_path):
    path = ckpt_lib.save(str(tmp_path), _ckpt_state(), epoch=0)
    meta = verify_npz(path)
    assert set(meta["crc32"]) >= {"['params']['w']", "['step']"}
    assert read_meta(path)["epoch"] == 0


def test_crc_detects_silent_single_bit_corruption(tmp_path):
    """Rewrite one entry with a flipped bit but a VALID zip container —
    only the per-entry CRC stamp can catch this class of corruption."""
    path = ckpt_lib.save(str(tmp_path), _ckpt_state(), epoch=0)
    with np.load(path) as z:
        data = {k: z[k] for k in z.files}
    arr = data["['params']['w']"].copy()
    arr.view(np.uint8)[0] ^= 1
    data["['params']['w']"] = arr
    with open(path, "wb") as f:  # valid archive, stale __meta__ CRCs
        np.savez(f, **data)
    with pytest.raises(CheckpointCorruptError, match="CRC32 mismatch"):
        verify_npz(path)


def test_restore_verify_catches_corruption_in_its_single_read(tmp_path):
    """The trainer ladder fuses CRC verification into restore's one
    decompression pass — restore(verify=True) must catch what a separate
    verify_npz pass would."""
    path = ckpt_lib.save(str(tmp_path), _ckpt_state(), epoch=0)
    with np.load(path) as z:
        data = {k: z[k] for k in z.files}
    arr = data["['params']['w']"].copy()
    arr.view(np.uint8)[0] ^= 1
    data["['params']['w']"] = arr
    with open(path, "wb") as f:  # valid archive, stale __meta__ CRCs
        np.savez(f, **data)
    with pytest.raises(CheckpointCorruptError, match="CRC32 mismatch"):
        ckpt_lib.restore(path, _ckpt_state(seed=9), verify=True)
    # unverified restore still loads it (the --no_ckpt_verify contract)
    ckpt_lib.restore(path, _ckpt_state(seed=9), verify=False)


def test_fused_epoch_refuses_stepwise_fault_clauses(tmp_path):
    """Step/batch-grain clauses would silently never fire under
    --fused_epoch (no step grain, loader bypassed) — refuse loudly."""
    cfg = _cfg(
        str(tmp_path), fused_epoch=True, steps_per_epoch=None,
        fault_plan="sigterm@epoch=1:step=0",
    )
    with pytest.raises(ValueError, match="fused_epoch compiles away"):
        Trainer(cfg)
    # ckpt-grain clauses stay legal under fused (epoch-boundary saves)
    t = Trainer(cfg.replace(fault_plan="ckpt_corrupt@epoch=7"))
    assert faults.active() is not None


def test_truncated_and_bitflipped_files_fail_verification(tmp_path):
    p0 = ckpt_lib.save(str(tmp_path), _ckpt_state(), epoch=0)
    p1 = ckpt_lib.save(str(tmp_path), _ckpt_state(), epoch=1)
    faults.truncate_file(p0, frac=0.4)
    faults.bitflip_file(p1, seed=3)
    with pytest.raises(CheckpointCorruptError):
        verify_npz(p0)
    with pytest.raises(CheckpointCorruptError):
        verify_npz(p1)


def test_sharded_verify_detects_corruption_and_quarantine_hides_it(tmp_path):
    d = str(tmp_path)
    mpath = ckpt_lib.save_sharded(d, _ckpt_state(), 0)
    assert ckpt_lib.verify_sharded(mpath)["epoch"] == 0  # clean roundtrip
    shard = next(n for n in os.listdir(d) if ".shard" in n)
    faults.bitflip_file(os.path.join(d, shard), seed=1)
    with pytest.raises(CheckpointCorruptError):
        ckpt_lib.verify_sharded(mpath)
    # quarantining the MANIFEST uncommits the checkpoint: invisible now
    ckpt_lib.quarantine(mpath)
    assert ckpt_lib.latest_sharded_checkpoint(d) is None


def test_sharded_verify_catches_missing_stamped_entry(tmp_path):
    """A valid zip that silently LOST an entry must fail verification (the
    restore would otherwise die mid-assembly instead of falling back)."""
    d = str(tmp_path)
    mpath = ckpt_lib.save_sharded(d, _ckpt_state(), 0)
    shard = os.path.join(d, next(n for n in os.listdir(d) if ".shard" in n))
    with np.load(shard) as z:
        data = {k: z[k] for k in z.files}
    dropped = next(k for k in data if k not in ("__crc__",))
    del data[dropped]
    with open(shard, "wb") as f:  # valid archive, entry gone
        np.savez(f, **data)
    with pytest.raises(CheckpointCorruptError, match="missing from archive"):
        ckpt_lib.verify_sharded(mpath)
    # shallow mode (multi-process restores) catches it too — it is a
    # directory-level property, no decompression needed
    with pytest.raises(CheckpointCorruptError, match="missing from archive"):
        ckpt_lib.verify_sharded(mpath, deep=False)


def test_stale_tmp_files_ignored_and_swept(tmp_path):
    d = str(tmp_path)
    ckpt_lib.save(d, _ckpt_state(), epoch=0)
    stray = os.path.join(d, "ckpt_5.npz.tmp")  # crash-leaked torn write
    with open(stray, "wb") as f:
        f.write(b"partial")
    # never reported as a checkpoint...
    assert latest_checkpoint(d) == (os.path.join(d, "ckpt_0.npz"), 0)
    # ...and the keep_last prune sweeps it
    ckpt_lib.save(d, _ckpt_state(), epoch=1, keep_last=5)
    assert not os.path.exists(stray)


def test_restore_ladder_quarantines_corrupt_newest_and_falls_back(tmp_path):
    d = str(tmp_path)
    cfg = _cfg(d)
    Trainer(cfg).fit()  # writes clean ckpt_0 and ckpt_1
    p1 = os.path.join(d, "ckpt_1.npz")
    faults.truncate_file(p1, frac=0.4)  # torn newest checkpoint
    t2 = Trainer(cfg.replace(resume=True))
    # fell back to epoch 0 (a restored clean ckpt_1 would give start_epoch 2)
    assert t2.start_epoch == 1
    assert os.path.exists(p1 + ".corrupt")  # quarantined, kept for forensics
    assert latest_checkpoint(d)[1] == 0  # the corrupt file is invisible now


# -- preemption (SIGTERM) ----------------------------------------------------


def test_sigterm_handler_sets_flag_cooperatively():
    token = preemption.install()
    try:
        assert not preemption.requested()
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5.0
        while not preemption.requested() and time.time() < deadline:
            time.sleep(0.01)
        assert preemption.requested()
    finally:
        preemption.clear()
        preemption.restore(token)


def test_sigterm_midepoch_emergency_saves_and_resume_is_bit_identical(
    tmp_path, golden
):
    gparams, glast = golden
    d = str(tmp_path)
    cfg = _cfg(d, fault_plan="sigterm@epoch=1:step=1")
    t = Trainer(cfg)
    with pytest.raises(PreemptedError):
        t.fit()
    # the in-flight step finished: exact snapshot of epoch 1 after 2 steps
    found = latest_checkpoint(d)
    assert found is not None and found[1] == 1
    assert read_meta(found[0])["mid_epoch_step"] == 2
    # resume (no fault plan) replays the identical remaining stream
    t2 = Trainer(cfg.replace(fault_plan=None, resume=True))
    assert t2.start_epoch == 1 and t2._resume_step == 2
    last = t2.fit()
    assert last["loss"] == glast["loss"]  # bit-identical, not just close
    assert _params_equal(jax.device_get(t2.state.params), gparams)


def test_sigterm_after_final_step_replays_the_epoch_record(tmp_path, golden):
    """The nastiest preemption point: SIGTERM lands after the epoch's LAST
    step, so the resumed epoch has zero steps left.  The snapshot stamps the
    final step's fetched metrics (``mid_epoch_metrics``) and the resume
    replays them, so the epoch record still matches the uninterrupted run
    bit-for-bit instead of being logged without a loss."""
    gparams, glast = golden
    d = str(tmp_path)
    cfg = _cfg(d, fault_plan="sigterm@epoch=1:step=2")
    with pytest.raises(PreemptedError):
        Trainer(cfg).fit()
    found = latest_checkpoint(d)
    assert found is not None and found[1] == 1
    meta = read_meta(found[0])
    assert meta["mid_epoch_step"] == 3  # every step of the epoch ran
    assert meta["mid_epoch_metrics"]["loss"] == glast["loss"]
    t2 = Trainer(cfg.replace(fault_plan=None, resume=True))
    assert t2.start_epoch == 1 and t2._resume_step == 3
    last = t2.fit()  # zero steps remain: the record is replayed, not empty
    assert last["loss"] == glast["loss"]
    assert _params_equal(jax.device_get(t2.state.params), gparams)


def test_cli_maps_preemption_to_distinct_exit_code(tmp_path):
    from tpu_dist.cli.train import main

    with pytest.raises(SystemExit) as ei:
        main([
            "--dataset", "synthetic", "--model", "tiny_mlp_rs",
            "--num_classes", "10", "--batch_size", "64", "--epochs", "2",
            "--steps_per_epoch", "3", "--eval_every", "0", "--save_every",
            "1", "--synthetic_n", "256", "--seed", "0", "--log_every", "50",
            "--ckpt_dir", str(tmp_path),
            "--fault_plan", "sigterm@epoch=0:step=1",
        ])
    assert ei.value.code == PREEMPTION_EXIT_CODE


def test_launcher_propagates_preemption_exit_code():
    import sys

    from tpu_dist.cli.launch import main as launch_main

    rc = launch_main([
        "--nproc", "2", "--",
        sys.executable, "-c",
        f"import sys; sys.exit({PREEMPTION_EXIT_CODE})",
    ])
    assert rc == PREEMPTION_EXIT_CODE


def test_launcher_crash_outranks_concurrent_preemption(tmp_path):
    """A child crashing for real while another is preempted must surface
    the CRASH code — '75, requeue me' would loop the orchestrator on a
    genuine bug forever."""
    import sys

    from tpu_dist.cli.launch import main as launch_main

    # rank 0 leaves only once rank 1 ignores SIGTERM: on a loaded host the
    # launcher's fail-fast SIGTERM otherwise reaches rank 1 while its
    # interpreter is still starting, and it dies on the raw signal (-15,
    # no crash) instead of exiting 1
    ready = str(tmp_path / "rank1_ready")
    code = (
        "import os, signal, sys, time\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "rank = int(sys.argv[sys.argv.index('--process_id') + 1])\n"
        f"ready = {ready!r}\n"
        "if rank == 1:\n"
        "    open(ready, 'w').close()\n"
        "    time.sleep(0.3)\n"
        "else:\n"
        "    while not os.path.exists(ready):\n"
        "        time.sleep(0.01)\n"
        f"sys.exit({PREEMPTION_EXIT_CODE} if rank == 0 else 1)\n"
    )
    rc = launch_main(["--nproc", "2", "--", sys.executable, "-c", code])
    assert rc == 1


def test_sigterm_during_fused_epoch_keeps_the_completed_epoch(tmp_path):
    """The fused path's cooperative point is the epoch boundary — and by
    then the epoch IS complete, so the emergency snapshot must file it
    under this epoch, not discard it as '0 steps done'."""
    d = str(tmp_path)
    cfg = _cfg(d, fused_epoch=True, steps_per_epoch=None)
    t = Trainer(cfg)
    orig = t._fused_runner

    def preempted_runner(state, *a, **kw):
        out = orig(state, *a, **kw)
        os.kill(os.getpid(), signal.SIGTERM)  # lands during the epoch
        return out

    t._fused_runner = preempted_runner
    with pytest.raises(PreemptedError):
        t.fit()
    found = latest_checkpoint(d)
    assert found is not None and found[1] == 0  # epoch 0's work survived
    assert "mid_epoch_step" not in read_meta(found[0])  # a CLEAN boundary
    assert Trainer(cfg.replace(resume=True)).start_epoch == 1


# -- NaN injection drives the existing auto-recover path ---------------------


def test_nan_fault_raises_divergence_without_auto_recover(tmp_path):
    cfg = _cfg(str(tmp_path), fault_plan="nan_loss@epoch=0:step=1")
    with pytest.raises(TrainingDivergedError, match="fault-injected"):
        Trainer(cfg).fit()


def test_nan_fault_fires_auto_recover_and_run_completes(tmp_path):
    d = str(tmp_path)
    cfg = _cfg(
        d, fault_plan="nan_loss@epoch=1:step=0", auto_recover=1,
        log_file=os.path.join(d, "hist.jsonl"),
    )
    t = Trainer(cfg)
    t.fit()  # epoch 0 saves; epoch 1 "diverges" once, recovers, completes
    assert t._lr_scale == cfg.recover_lr_factor  # backoff applied
    with open(os.path.join(d, "hist.jsonl")) as f:
        assert any('"auto_recover"' in line for line in f)
    assert latest_checkpoint(d)[1] == 1  # the rerun epoch finished and saved


# -- loader hang-proofing ----------------------------------------------------


def test_loader_producer_death_raises_instead_of_hanging():
    mesh = mesh_lib.data_parallel_mesh()
    imgs, lbls = synthetic_cifar(128, 10, seed=1)
    faults.install("loader_stall@batch=1")
    dl = DataLoader(
        imgs, lbls, 32, DistributedSampler(128, 1, 0), mesh, seed=0,
        watchdog_timeout=0.2,
    )
    got = 0
    t0 = time.time()
    with pytest.raises(LoaderProducerDiedError, match="producer thread died"):
        for _ in dl:
            got += 1
    assert got == 1  # batch 0 arrived; the producer died before batch 1
    assert time.time() - t0 < 30.0  # watchdog, not a hang


@pytest.mark.slow  # real sleeps: excluded from the timed tier-1 gate
def test_real_clock_backoff_actually_sleeps(tmp_path):
    """The injectable-clock tests above patch sleep; this exercises the
    REAL time.sleep path the production writer uses."""
    ckpt_lib.set_io_retries(2)
    faults.install("ckpt_write@call=1:times=2")
    t0 = time.time()
    path = ckpt_lib.save(str(tmp_path), _ckpt_state(), epoch=0)
    assert time.time() - t0 >= 0.15  # the 0.05 + 0.1 schedule really ran
    verify_npz(path)


@pytest.mark.slow  # waits out the default 5s watchdog tick
def test_loader_watchdog_fires_at_default_timeout():
    mesh = mesh_lib.data_parallel_mesh()
    imgs, lbls = synthetic_cifar(128, 10, seed=1)
    faults.install("loader_stall@batch=0")
    dl = DataLoader(imgs, lbls, 32, DistributedSampler(128, 1, 0), mesh, seed=0)
    t0 = time.time()
    with pytest.raises(LoaderProducerDiedError):
        for _ in dl:
            pass
    assert time.time() - t0 < 60.0  # bounded by the watchdog, not a hang


def test_loader_unfaulted_epoch_still_completes():
    mesh = mesh_lib.data_parallel_mesh()
    imgs, lbls = synthetic_cifar(128, 10, seed=1)
    dl = DataLoader(
        imgs, lbls, 32, DistributedSampler(128, 1, 0), mesh, seed=0,
        watchdog_timeout=0.2,
    )
    assert sum(1 for _ in dl) == len(dl)


# -- the traced step is unchanged when a plan is armed -----------------------


def test_fault_injection_points_are_traced_noops():
    from tpu_dist.analysis.jaxpr_audit import fault_noop_violations

    assert fault_noop_violations() == []


# -- the composite acceptance scenario ---------------------------------------


def test_composite_chaos_run_finishes_bit_identical_to_golden(
    tmp_path, golden
):
    """ISSUE 3 acceptance: transient ckpt-write EIO + SIGTERM mid-epoch +
    corrupt newest checkpoint → emergency save, restart, quarantine,
    fallback to the integrity-verified snapshot, finish bit-identical."""
    gparams, glast = golden
    d = str(tmp_path)
    plan = (
        "ckpt_write@call=1:times=1;"        # EIO on the first write attempt
        "sigterm@epoch=1:step=0;"           # preempted mid-epoch 1
        "ckpt_corrupt@epoch=1:mode=truncate"  # ...and the emergency snapshot tears
    )
    cfg = _cfg(d, fault_plan=plan, ckpt_io_retries=2)
    t = Trainer(cfg)
    with pytest.raises(PreemptedError):
        t.fit()
    # the transient EIO was retried: clean ckpt_0 exists and verifies
    verify_npz(os.path.join(d, "ckpt_0.npz"))
    # restart: the torn emergency ckpt_1 is quarantined, ckpt_0 restores
    t2 = Trainer(cfg.replace(fault_plan=None, resume=True))
    assert os.path.exists(os.path.join(d, "ckpt_1.npz.corrupt"))
    assert t2.start_epoch == 1 and t2._resume_step == 0
    last = t2.fit()  # re-runs epoch 1 from the clean boundary
    assert last["loss"] == glast["loss"]
    assert _params_equal(jax.device_get(t2.state.params), gparams)
