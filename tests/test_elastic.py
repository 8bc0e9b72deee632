"""Elastic training (docs/resilience.md "Elastic training"): mesh-shape-
portable checkpoints, the consumed-prefix sampler re-partition, the
launcher's shrink-on-failure supervisor, and the TD111 traced-noop gate.

The world-size changes here are driven two ways: in-process by handing the
Trainer a smaller device mesh (8 emulated CPU devices -> a 4-device mesh —
full fidelity for the state-remap path, deterministic and fast), and
out-of-process through ``cli/launch.py``'s elastic supervisor with stub
children (the relaunch policy without jax in the loop). The full
multi-phase subprocess drill is ``python -m tpu_dist.elastic.drill``
(``make elastic-drill``), exercised by a slow-marked test here.
"""

import json
import os
import signal
import sys

import jax
import numpy as np
import pytest

from tpu_dist.ckpt import checkpoint as ckpt_lib
from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.comm.quantize import padded_len
from tpu_dist.config import TrainConfig
from tpu_dist.data import DistributedSampler
from tpu_dist.elastic import supervisor as sup
from tpu_dist.elastic.errors import ConfigMismatchError, ElasticShapeMismatch
from tpu_dist.elastic.remap import (
    Remapper,
    classify,
    elastic_stamp,
    make_remapper,
    params_len,
)
from tpu_dist.obs import counters as counters_lib
from tpu_dist.resilience import faults, preemption
from tpu_dist.resilience.preemption import PREEMPTION_EXIT_CODE, PreemptedError
from tpu_dist.train.state import TrainState
from tpu_dist.train.trainer import Trainer, register_model
from tests.helpers import TinyMLP

# TinyMLP(10, width=16, in_dim=3072) ravels to L = 49338 ≡ 2 (mod 8), so
# padded_len(L, 8) = 49344 != 49340 = padded_len(L, 4): the 8->4 shrink
# genuinely reshapes the ZeRO-1 flat vectors (and the EF residual row
# count always changes with the extent) — the remap path cannot be
# vacuously green.
register_model(
    "tiny_mlp_el", lambda num_classes=10: TinyMLP(num_classes, width=16, in_dim=3072)
)

L_TINY = 3072 * 16 + 16 + 16 * 10 + 10  # 49338


@pytest.fixture(autouse=True)
def _clean_fault_state():
    faults.clear()
    preemption.clear()
    prev = ckpt_lib.set_io_retries(0)
    yield
    faults.clear()
    preemption.clear()
    ckpt_lib.set_io_retries(prev)


def _cfg(ckpt_dir, **kw):
    base = dict(
        dataset="synthetic", model="tiny_mlp_el", num_classes=10,
        batch_size=64, epochs=2, steps_per_epoch=3, log_every=50,
        eval_every=0, save_every=1, synthetic_n=256, seed=0,
        ckpt_dir=ckpt_dir, num_workers=1,
    )
    base.update(kw)
    return TrainConfig(**base)


def _mesh(n):
    return mesh_lib.data_parallel_mesh(jax.devices()[:n])


def _flat_ckpt(path):
    with np.load(path) as z:
        return {k: np.array(z[k]) for k in z.files if k != "__meta__"}


# -- remap unit layer: the (n_old, n_new) property sweep ---------------------


@pytest.mark.parametrize(
    "n_old,n_new",
    [(8, 4), (4, 8), (8, 2), (2, 8), (8, 3), (3, 8), (6, 4), (2, 5),
     (1, 8), (8, 1)],
)
def test_remap_round_trip_reconstructs_global_arrays(n_old, n_new):
    """Grow and shrink, divisor and non-divisor: the ZeRO-1 flat vector's
    logical prefix is copied bit-exactly (zero tails both sides), r2 is
    bit-exact per coordinate, and r1's aggregate (the sum over replica
    rows — the only thing the next reduce sees) is preserved exactly."""
    L = 37
    rng = np.random.default_rng(n_old * 100 + n_new)
    p_old, p_new = padded_len(L, n_old), padded_len(L, n_new)

    mom = np.zeros(p_old, np.float32)
    mom[:L] = rng.normal(size=L).astype(np.float32)
    r1 = rng.normal(size=(n_old * p_old,)).astype(np.float32)
    r2 = np.zeros(p_old, np.float32)
    r2[:L] = rng.normal(size=L).astype(np.float32)

    rm = Remapper(L, n_new, n_old=n_old)
    out_mom = rm("['opt_state']", mom, np.zeros(p_new, np.float32))
    assert out_mom.dtype == np.float32
    np.testing.assert_array_equal(out_mom[:L], mom[:L])  # bit-exact
    assert not out_mom[L:].any()

    out_r1 = rm("['ef']['r1']", r1, np.zeros(n_new * p_new, np.float32))
    rows_old = r1.reshape(n_old, p_old)
    rows_new = out_r1.reshape(n_new, p_new)
    crop = min(L, p_old, p_new)
    np.testing.assert_array_equal(
        rows_new.sum(axis=0, dtype=np.float32)[:crop],
        rows_old[:, :crop].sum(axis=0, dtype=np.float32),
    )  # aggregate residual preserved to the bit
    assert not rows_new[1:].any()  # folded into replica 0

    out_r2 = rm("['ef']['r2']", r2, np.zeros(p_new, np.float32))
    np.testing.assert_array_equal(out_r2[:L], r2[:L])
    assert not out_r2[L:].any()
    assert len(rm.used) == 3


def test_remap_refuses_nonzero_tail_and_unknown_keys():
    L = 10
    rm = Remapper(L, 4, n_old=8)
    bad = np.ones(16, np.float32)  # nonzero past L: not the ZeRO-1 layout
    with pytest.raises(ConfigMismatchError, match="nonzero"):
        rm("['opt_state']['mu']", bad, np.zeros(12, np.float32))
    # a params-shaped leaf is never elastic — the hook declines (None)
    assert rm("['params']['w']", np.zeros((4, 3)), np.zeros((2, 3))) is None


def test_remap_r1_requires_the_dp_stamp():
    rm = Remapper(10, 4)  # n_old unknown (pre-stamp checkpoint)
    with pytest.raises(ConfigMismatchError, match="stamp"):
        rm("['ef']['r1']", np.zeros(96, np.float32), np.zeros(48, np.float32))


def test_classify_and_stamp():
    assert classify("['ef']['r1']", (96,), (48,), 10) == "ef_r1"
    assert classify("['ef']['r2']", (12,), (10,), 10) == "ef_r2"
    assert classify("['opt_state']['mu']", (16,), (12,), 10) == "zero1_flat"
    assert classify("['opt_state']['w1']", (4, 3), (2, 3), 10) is None
    assert classify("['params']['w']", (16,), (12,), 10) is None
    st = elastic_stamp(8, 2, 49338)
    assert st == {"dp": 8, "procs": 2, "params_len": 49338}


def test_make_remapper_rejects_a_different_model():
    state = TrainState(
        params={"w": np.zeros(10, np.float32)}, bn_state={}, opt_state=(),
        step=np.asarray(0, np.int32),
    )
    with pytest.raises(ConfigMismatchError, match="different model"):
        make_remapper(state, {"elastic": {"dp": 8, "params_len": 99}}, 4)
    rm = make_remapper(state, {"elastic": {"dp": 8, "params_len": 10}}, 4)
    assert rm.n_old == 8 and rm.L == params_len(state.params) == 10


def test_ckpt_raises_typed_errors_without_a_remapper(tmp_path):
    """The restore-ladder split: a dp-extent shape change is the BENIGN
    typed error (ElasticShapeMismatch — retry with a remapper); a param
    shape change is ConfigMismatchError. Both stay ValueError for old
    callers."""
    L = 37
    params = {"w": np.arange(L, dtype=np.float32)}
    st8 = TrainState(params, {}, np.zeros(padded_len(L, 8), np.float32),
                     np.asarray(0, np.int32))
    path = ckpt_lib.save(str(tmp_path), st8, epoch=0)
    tmpl4 = TrainState(params, {}, np.zeros(padded_len(L, 3), np.float32),
                       np.asarray(0, np.int32))
    with pytest.raises(ElasticShapeMismatch) as ei:
        ckpt_lib.restore(path, tmpl4)
    assert isinstance(ei.value, ValueError)
    assert ei.value.key == "['opt_state']"
    bad = TrainState({"w": np.zeros(L + 1, np.float32)}, {},
                     np.zeros(padded_len(L, 8), np.float32),
                     np.asarray(0, np.int32))
    with pytest.raises(ConfigMismatchError, match="shape mismatch"):
        ckpt_lib.restore(path, bad)


def test_sharded_restore_remaps_across_extents(tmp_path):
    """Sharded format: a ZeRO-1 flat vector saved as 8 device slices
    reassembles (allgather-then-reslice) and remaps onto a 4-device
    template bit-exactly; world-size-independent leaves reslice as
    before."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    # w (8,3) + b (2,) ravel to L = 26 ≡ 2 (mod 8): padded_len(26, 8) = 32
    # vs padded_len(26, 4) = 28 — the flat vector genuinely reshapes
    L = 26
    mesh8, mesh4 = _mesh(8), _mesh(4)
    w = np.arange(24, dtype=np.float32).reshape(8, 3)
    b = np.asarray([7.0, 9.0], np.float32)
    mom = np.zeros(padded_len(L, 8), np.float32)
    mom[:L] = np.arange(L, dtype=np.float32) * 1e-3
    st8 = TrainState(
        params={
            "b": jax.device_put(b, NamedSharding(mesh8, P())),
            "w": jax.device_put(w, NamedSharding(mesh8, P("data"))),
        },
        bn_state={},
        opt_state=jax.device_put(mom, NamedSharding(mesh8, P("data"))),
        step=jax.device_put(np.asarray(5, np.int32), NamedSharding(mesh8, P())),
    )
    mpath = ckpt_lib.save_sharded(
        str(tmp_path), st8, 0, extra_meta={"elastic": elastic_stamp(8, 1, L)}
    )
    tmpl4 = TrainState(
        params={
            "b": jax.device_put(np.zeros_like(b), NamedSharding(mesh4, P())),
            "w": jax.device_put(
                np.zeros_like(w), NamedSharding(mesh4, P("data"))
            ),
        },
        bn_state={},
        opt_state=jax.device_put(
            np.zeros(padded_len(L, 4), np.float32), NamedSharding(mesh4, P("data"))
        ),
        step=jax.device_put(np.asarray(0, np.int32), NamedSharding(mesh4, P())),
    )
    with pytest.raises(ElasticShapeMismatch):
        ckpt_lib.restore_sharded(mpath, tmpl4)
    rm = make_remapper(tmpl4, ckpt_lib.read_sharded_meta(mpath), 4)
    out = ckpt_lib.restore_sharded(mpath, tmpl4, remap=rm)
    np.testing.assert_array_equal(np.asarray(out.params["w"]), w)
    np.testing.assert_array_equal(np.asarray(out.params["b"]), b)
    got = np.asarray(out.opt_state)
    assert got.shape == (padded_len(L, 4),)
    np.testing.assert_array_equal(got[:L], mom[:L])
    assert not got[L:].any()
    assert rm.used == [("['opt_state']", "zero1_flat")]
    assert int(np.asarray(out.step)) == 5


def test_missing_ef_cold_start_survives_a_world_change(tmp_path):
    """A pre-EF checkpoint restored at a NEW extent with int8_ef on:
    residuals cold-start at zeros shaped for the new world."""
    L = 37
    params = {"w": np.arange(L, dtype=np.float32)}
    st8 = TrainState(params, {}, np.zeros(padded_len(L, 8), np.float32),
                     np.asarray(0, np.int32))  # no ef saved
    path = ckpt_lib.save(
        str(tmp_path), st8, epoch=0,
        extra_meta={"elastic": elastic_stamp(8, 1, L)},
    )
    p4 = padded_len(L, 4)
    tmpl = TrainState(
        params, {}, np.zeros(p4, np.float32), np.asarray(0, np.int32),
        ef={"r1": np.zeros(4 * p4, np.float32)},
    )
    out = ckpt_lib.restore(
        path, tmpl, remap=make_remapper(tmpl, ckpt_lib.read_meta(path), 4)
    )
    assert out.ef["r1"].shape == (4 * p4,) and not out.ef["r1"].any()
    np.testing.assert_array_equal(np.asarray(out.opt_state)[:L], np.zeros(L))


# -- sampler: consumed-prefix re-partitioning --------------------------------


def test_sampler_offset_repartitions_without_drop_or_dup():
    """4 shards consume k global batches; 2 NEW shards with the offset
    pick up exactly the not-yet-seen examples — union equals the full
    epoch, no example dropped or double-seen."""
    N, n_old, n_new, gbatch, k = 120, 4, 2, 20, 2
    old = [DistributedSampler(N, n_old, j, seed=7) for j in range(n_old)]
    for s in old:
        s.set_epoch(3)
    per_old = gbatch // n_old
    consumed = np.concatenate(
        [s.indices()[: k * per_old] for s in old]
    )
    order = np.random.default_rng(7 + 3).permutation(N)
    # lockstep shards => the union of per-shard prefixes IS the global prefix
    assert sorted(consumed) == sorted(order[: k * gbatch])

    new = [DistributedSampler(N, n_new, j, seed=7) for j in range(n_new)]
    remaining = []
    for s in new:
        s.set_epoch(3)
        s.set_offset(k * gbatch)
        remaining.append(s.indices())
    rest = np.concatenate(remaining)
    assert sorted(np.concatenate([consumed, rest])) == sorted(range(N))
    # next epoch: set_epoch clears the offset — full partition again
    for s in new:
        s.set_epoch(4)
        assert s.offset == 0 and len(s) == -(-N // n_new)


def test_sampler_offset_equals_iter_from_for_same_world():
    """Same shard count: the offset path is exactly the per-shard stream
    suffix iter_from consumes — the strict generalization claim."""
    N, n, gbatch, k = 128, 4, 16, 3
    for j in range(n):
        a = DistributedSampler(N, n, j, seed=5)
        a.set_epoch(1)
        suffix = a.indices()[k * (gbatch // n):]
        b = DistributedSampler(N, n, j, seed=5)
        b.set_epoch(1)
        b.set_offset(k * gbatch)
        np.testing.assert_array_equal(b.indices(), suffix)


def test_sampler_offset_validation():
    s = DistributedSampler(10, 2, 0)
    with pytest.raises(ValueError):
        s.set_offset(-1)
    with pytest.raises(ValueError):
        s.set_offset(11)


# -- trainer e2e: in-process world shrink ------------------------------------


def test_trainer_shrink_resume_zero1_ef_is_bit_exact(tmp_path):
    """The tentpole e2e at the state layer: a ZeRO-1 + int8_ef run saved
    at 8 devices resumes onto a 4-device mesh — params/momentum logical
    content bit-identical, EF aggregate preserved, resharded counted —
    and keeps training at the new extent."""
    d = str(tmp_path)
    log = os.path.join(d, "run.jsonl")
    cfg = _cfg(d, shard_weight_update=True, grad_compression="int8_ef",
               log_file=log)
    t = Trainer(cfg)
    t.fit()
    ck = ckpt_lib.latest_checkpoint(d)
    assert ck is not None and ck[1] == 1
    saved = _flat_ckpt(ck[0])
    meta = ckpt_lib.read_meta(ck[0])
    assert meta["elastic"] == {"dp": 8, "procs": 1, "params_len": L_TINY}
    old_r1 = saved["['ef']['r1']"].reshape(8, padded_len(L_TINY, 8))

    t2 = Trainer(cfg.replace(resume=True), mesh=_mesh(4))
    assert t2.start_epoch == 2
    assert counters_lib.get("resume.resharded") == 1
    # params: world-size-independent, bit-identical
    for (path_a, a) in jax.tree_util.tree_flatten_with_path(t2.state.params)[0]:
        key = jax.tree_util.keystr(path_a)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), saved[f"['params']{key}"]
        )
    # ZeRO-1 momentum: logical prefix bit-identical, new tail zero
    mom = np.asarray(jax.device_get(t2.state.opt_state))
    assert mom.shape == (padded_len(L_TINY, 4),)
    np.testing.assert_array_equal(mom[:L_TINY], saved["['opt_state']"][:L_TINY])
    assert not mom[L_TINY:].any()
    # EF r1: aggregate residual preserved exactly at the new extent
    r1 = np.asarray(jax.device_get(t2.state.ef["r1"])).reshape(
        4, padded_len(L_TINY, 4)
    )
    np.testing.assert_array_equal(
        r1.sum(axis=0, dtype=np.float32)[:L_TINY],
        old_r1[:, :L_TINY].sum(axis=0, dtype=np.float32),
    )
    # ...and the shrunk trainer actually trains an epoch at dp=4
    last = t2.fit(3)
    assert np.isfinite(last["loss"]) and last["steps"] == 3
    # observability: the resume record marks the segment boundary
    recs = [json.loads(l) for l in open(log)]
    resumes = [r for r in recs if r.get("kind") == "resume"]
    assert resumes and resumes[-1]["resharded"] is True
    assert resumes[-1]["dp"] == 4 and resumes[-1]["prev_dp"] == 8
    assert counters_lib.snapshot()["elastic.world_size"] == 4


def test_sigterm_midepoch_then_shrink_matches_golden(tmp_path):
    """ISSUE 10 acceptance (in-process half): SIGTERM an 8-device ZeRO-1
    run mid-epoch; the emergency snapshot is exact; resume on 4 devices
    restores it bit-identically (logical content) and the continued loss
    trajectory matches the uninterrupted golden run within the
    golden-trajectory tolerance."""
    gdir = str(tmp_path / "golden")
    cfg_g = _cfg(gdir, shard_weight_update=True)
    tg = Trainer(cfg_g)
    glast = tg.fit()
    gparams = jax.device_get(tg.state.params)

    d = str(tmp_path / "elastic")
    cfg = _cfg(d, shard_weight_update=True,
               fault_plan="sigterm@epoch=1:step=1")
    t = Trainer(cfg)
    with pytest.raises(PreemptedError):
        t.fit()
    ck = ckpt_lib.latest_checkpoint(d)
    assert ck is not None and ck[1] == 1
    meta = ckpt_lib.read_meta(ck[0])
    assert meta["mid_epoch_step"] == 2
    assert meta["mid_epoch_examples"] == 2 * 64 and meta["mid_epoch_procs"] == 1
    saved = _flat_ckpt(ck[0])

    t2 = Trainer(
        cfg.replace(fault_plan=None, resume=True), mesh=_mesh(4)
    )
    assert t2.start_epoch == 1 and t2._resume_step == 2
    # allgathered restored state == the emergency save, bit-exact where
    # dtype allows (params verbatim; momentum's logical prefix)
    for (path_a, a) in jax.tree_util.tree_flatten_with_path(t2.state.params)[0]:
        key = jax.tree_util.keystr(path_a)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), saved[f"['params']{key}"]
        )
    mom = np.asarray(jax.device_get(t2.state.opt_state))
    np.testing.assert_array_equal(mom[:L_TINY], saved["['opt_state']"][:L_TINY])
    last = t2.fit()
    # different reduce extent => float-order differences only: the
    # existing golden-trajectory tolerance
    np.testing.assert_allclose(last["loss"], glast["loss"], rtol=2e-3)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(t2.state.params)),
        jax.tree_util.tree_leaves(gparams),
    ):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-5)


def test_offset_resume_runs_only_the_remaining_examples(tmp_path):
    """A mid-epoch snapshot stamped from a DIFFERENT process count drops
    the per-shard step replay and re-enters via the consumed-example
    offset: the resumed epoch runs exactly the remaining global batches."""
    d = str(tmp_path)
    cfg = _cfg(d, epochs=1)
    t = Trainer(cfg)
    ckpt_lib.save(
        d, t.state, epoch=0,
        extra_meta={
            "mid_epoch_step": 1, "mid_epoch_batch_size": 64,
            "mid_epoch_seed": 0, "mid_epoch_procs": 2,
            "mid_epoch_examples": 64,
            "elastic": elastic_stamp(8, 2, L_TINY),
        },
    )
    t2 = Trainer(cfg.replace(resume=True))
    assert t2.start_epoch == 0
    assert t2._resume_step == 0 and t2._resume_examples == 64
    last = t2.fit()
    # 256 examples, 64 consumed -> 3 of the 4 global batches remain
    assert last["steps"] == 3
    # a SECOND mid-epoch stamp from inside the offset epoch carries the
    # cumulative example position (offset + steps * global batch)
    meta = ckpt_lib.read_meta(ckpt_lib.latest_checkpoint(d)[0])
    assert "mid_epoch_step" not in meta  # clean end-of-epoch save


def test_mid_epoch_examples_stamp_clamps_to_dataset(tmp_path):
    """The final batch of a drop_last=False epoch is wrap-around padded
    (steps * global_batch can exceed N): the examples stamp clamps to the
    dataset size so a later elastic resume's set_offset can never be
    asked for a position outside the epoch."""
    cfg = _cfg(str(tmp_path), synthetic_n=200)  # 4 padded steps of 64
    t = Trainer(cfg)
    pos = t._mid_epoch_position(4)
    assert pos["mid_epoch_examples"] == 200  # min(4 * 64, N)
    assert pos["mid_epoch_step"] == 4
    # and a (legally) end-of-data offset resumes as an empty epoch
    s = DistributedSampler(200, 1, 0)
    s.set_offset(200)
    assert len(s) == 0 and s.indices().size == 0


# -- faults: rank_kill clause ------------------------------------------------


def test_rank_kill_clause_parses_and_matches(monkeypatch):
    plan = faults.FaultPlan.parse("rank_kill@step=2:rank=3")
    assert plan.clauses[0].site == "rank_kill"
    assert plan.clauses[0].params == {"step": 2, "rank": 3}
    with pytest.raises(faults.FaultPlanError, match="missing required"):
        faults.FaultPlan.parse("rank_kill@step=2")  # rank is required

    kills = []
    monkeypatch.setattr(faults.os, "kill", lambda pid, sig: kills.append(sig))
    faults.install("rank_kill@step=2:rank=3")
    assert faults.on_step(0, 2, rank=0) == frozenset()  # wrong rank
    assert faults.on_step(0, 2, rank=None) == frozenset()  # unknown rank
    assert faults.RANK_KILL in faults.on_step(0, 2, rank=3)
    assert kills == [signal.SIGKILL]
    assert faults.on_step(0, 2, rank=3) == frozenset()  # one-shot


def test_fused_epoch_refuses_rank_kill(tmp_path):
    cfg = _cfg(str(tmp_path), fused_epoch=True, steps_per_epoch=None,
               fault_plan="rank_kill@step=0:rank=0")
    with pytest.raises(ValueError, match="fused_epoch compiles away"):
        Trainer(cfg)


# -- supervisor policy -------------------------------------------------------


def test_next_world_size_policy():
    assert sup.feasible_sizes(8) == [8, 4, 2, 1]
    assert sup.next_world_size(8, survivors=7, min_procs=1) == 4
    assert sup.next_world_size(8, survivors=4, min_procs=1) == 4
    assert sup.next_world_size(8, survivors=3, min_procs=1) == 2
    assert sup.next_world_size(8, survivors=3, min_procs=4) is None
    assert sup.next_world_size(6, survivors=5, min_procs=1) == 3
    assert sup.next_world_size(8, survivors=0, min_procs=1) is None


def test_supervise_shrinks_retries_and_gives_up():
    calls = []
    sleeps = []

    def rounds(n, restart):
        calls.append((n, restart))
        if restart == 0:
            # rank 2 died hard, the rest preempted: 3 survivors of 4
            return sup.RoundResult(
                PREEMPTION_EXIT_CODE,
                {0: 75, 1: 75, 2: -signal.SIGKILL, 3: 75},
            )
        return sup.RoundResult(0, {i: 0 for i in range(n)})

    rc = sup.supervise(
        rounds, nproc=4, min_procs=1, max_restarts=3,
        backoff_base=0.5, sleep=sleeps.append,
    )
    assert rc == 0
    assert calls == [(4, 0), (2, 1)]  # largest divisor of 4 staffed by 3
    assert sleeps == [0.5]  # deterministic backoff, injectable

    # whole-pod preemption retries at the SAME size
    calls.clear()

    def rounds2(n, restart):
        calls.append((n, restart))
        if restart == 0:
            return sup.RoundResult(75, {i: 75 for i in range(n)})
        return sup.RoundResult(0, {i: 0 for i in range(n)})

    assert sup.supervise(rounds2, nproc=4, min_procs=2, max_restarts=2,
                         sleep=lambda _s: None) == 0
    assert calls == [(4, 0), (4, 1)]

    # budget exhaustion surfaces the real exit code
    assert sup.supervise(
        lambda n, r: sup.RoundResult(1, {0: 1}),
        nproc=1, min_procs=1, max_restarts=2, sleep=lambda _s: None,
    ) == 1

    # below the floor: give up with the round's code
    assert sup.supervise(
        lambda n, r: sup.RoundResult(75, {0: 75, 1: -signal.SIGKILL}),
        nproc=2, min_procs=2, max_restarts=5, sleep=lambda _s: None,
    ) == 75

    # the launcher's own SIGTERM stands elastic down
    assert sup.supervise(
        lambda n, r: sup.RoundResult(75, {i: 75 for i in range(n)}),
        nproc=2, min_procs=1, max_restarts=5, sleep=lambda _s: None,
        should_continue=lambda: False,
    ) == 75

    # ...including when the stop request lands DURING the backoff sleep:
    # no fresh world may spawn after it
    rounds_run = []
    stop = [False]

    def stopping_sleep(_s):
        stop[0] = True

    rc = sup.supervise(
        lambda n, r: (rounds_run.append((n, r)) or
                      sup.RoundResult(75, {i: 75 for i in range(n)})),
        nproc=2, min_procs=1, max_restarts=5, sleep=stopping_sleep,
        should_continue=lambda: not stop[0],
    )
    assert rc == 75 and rounds_run == [(2, 0)]  # round 1 never spawned


def test_launcher_elastic_relaunches_stub_children(tmp_path):
    """cli/launch.py e2e with stub children (no jax): round 0 loses rank
    2 to a SIGKILL while the others preempt; the supervisor relaunches
    at world size 2 with --resume injected and the restart env stamped."""
    from tpu_dist.cli.launch import main as launch_main

    marker = str(tmp_path / "world.txt")
    child = (
        "import os, signal, sys, time\n"
        "argv = sys.argv\n"
        "rank = int(argv[argv.index('--process_id') + 1])\n"
        "n = int(argv[argv.index('--num_processes') + 1])\n"
        "if '--resume' in argv:\n"
        f"    open({marker!r}, 'a').write(\n"
        "        f\"{n} {os.environ.get('TPU_DIST_ELASTIC_RESTARTS')}\\n\")\n"
        "    sys.exit(0)\n"
        "if rank == 2:\n"
        "    os.kill(os.getpid(), signal.SIGKILL)\n"
        "signal.signal(signal.SIGTERM, lambda *a: sys.exit(75))\n"
        "time.sleep(30)\n"
    )
    rc = launch_main([
        "--nproc", "4", "--elastic_min_procs", "1",
        "--elastic_max_restarts", "2", "--elastic_backoff", "0.01", "--",
        sys.executable, "-c", child,
    ])
    assert rc == 0
    lines = open(marker).read().split()
    assert lines == ["2", "1", "2", "1"]  # 2 ranks, restart #1


def test_launcher_non_elastic_path_unchanged():
    """Without --elastic_min_procs the launcher is the single-round tool
    it always was: a preemption propagates 75, no relaunch."""
    from tpu_dist.cli.launch import main as launch_main

    rc = launch_main([
        "--nproc", "2", "--",
        sys.executable, "-c", f"import sys; sys.exit({PREEMPTION_EXIT_CODE})",
    ])
    assert rc == PREEMPTION_EXIT_CODE


# -- observability satellites ------------------------------------------------


def _resume_rec(run_id, ts, rel_s, **kw):
    rec = {"kind": "resume", "run_id": run_id, "ts": ts, "rel_s": rel_s,
           "schema_version": 7}
    rec.update(kw)
    return rec


def test_summarize_renders_world_size_segments():
    from tpu_dist.obs.summarize import format_text, summarize

    records = [
        {"kind": "train_epoch", "epoch": 0, "run_id": "a", "ts": 1.0,
         "rel_s": 1.0, "schema_version": 7, "epoch_time": 1.0,
         "images_per_sec": 100.0, "loss": 2.0},
        _resume_rec("b", 10.0, 0.5, epoch=1, world=4, dp=4, prev_dp=8,
                    resharded=True, restarts=1, mid_epoch_step=2),
        {"kind": "train_epoch", "epoch": 1, "run_id": "b", "ts": 11.0,
         "rel_s": 1.5, "schema_version": 7, "epoch_time": 1.0,
         "images_per_sec": 50.0, "loss": 1.5},
    ]
    rep = summarize(records)
    assert rep["resumes"][0]["resharded"] is True
    # the first (fresh) segment logs no resume record: its extent is
    # seeded from the resumed checkpoint's prev_dp stamp
    assert rep["world_sizes"] == [8, 4]
    text = format_text(rep)
    assert "world size changed mid-run (elastic): dp 8 -> 4" in text
    assert "RESHARDED from dp=8" in text
    assert "elastic restart #1" in text
    assert not rep["skipped_kinds"]  # 'resume' is a KNOWN kind now


def test_run_ledger_charges_reshard_gap_to_recovery():
    from tpu_dist.obs import goodput

    def gp(run, ts, rel, **kw):
        rec = {"kind": "goodput", "run_id": run, "ts": ts, "rel_s": rel}
        rec.update(kw)
        return rec

    records = [
        gp("a", 10.0, 5.0, final=True, productive_s=4.0, elapsed_s=5.0,
           goodput_frac=0.8),
        # 6s relaunch gap; the new segment opens with a RESHARDED resume
        _resume_rec("b", 16.0, 0.0, epoch=1, dp=4, prev_dp=8, resharded=True),
        gp("b", 20.0, 4.0, final=True, productive_s=3.0, elapsed_s=4.0,
           goodput_frac=0.75),
    ]
    led = goodput.run_ledger(records)
    assert led["n_segments"] == 2
    assert led["restart_gap_s"] == pytest.approx(6.0)
    assert led["recovery_s"] == pytest.approx(6.0)  # reshard, not preempt
    assert led["preempt_s"] == pytest.approx(0.0)
    assert led["elapsed_s"] == pytest.approx(5.0 + 4.0 + 6.0)

    # a plain (non-resharded) restart still charges preempt_s
    records[1] = _resume_rec("b", 16.0, 0.0, epoch=1, dp=8, resharded=False)
    led = goodput.run_ledger(records)
    assert led["preempt_s"] == pytest.approx(6.0)
    assert led["recovery_s"] == pytest.approx(0.0)


def test_tail_renders_resume_segment_line():
    from tpu_dist.obs.tail import TailState

    st = TailState()
    st.add([
        _resume_rec("a", 1.0, 0.0, epoch=1, world=4, dp=4, prev_dp=8,
                    resharded=True, restarts=1),
    ])
    assert any("RESHARDED from dp=8" in e for e in st.events)
    assert any("restart #1" in e for e in st.events)


def test_pod_report_surfaces_world_changes():
    from tpu_dist.obs.aggregate import format_text, pod_report

    records = [
        _resume_rec("a", 1.0, 0.0, epoch=0, world=8, dp=8, resharded=False),
        _resume_rec("b", 9.0, 0.0, epoch=1, world=4, dp=4, prev_dp=8,
                    resharded=True),
    ]
    rep = pod_report([("host0", records)])
    assert rep["hosts"][0]["world_sizes"] == [8, 4]
    assert "elastic on host0" in format_text(rep)


# -- TD111: elastic resume is invisible to the compiled program --------------


def test_td111_registered_and_gate_passes():
    from tpu_dist.analysis.jaxpr_audit import elastic_resume_noop_violations
    from tpu_dist.analysis.rules import RULES

    assert "TD111" in RULES and RULES["TD111"].name == "elastic-resume-not-noop"
    assert elastic_resume_noop_violations() == []


@pytest.mark.slow  # two multi-process training rounds (compiles included)
def test_launcher_elastic_real_training_round_trip(tmp_path):
    """The launcher supervisor over REAL multi-process training: a 2-process
    run is preempted mid-epoch (deterministic sigterm fault at epoch 1 step
    0, with a collective mid-epoch snapshot landing first), the supervisor
    relaunches with --resume, and the relaunched world finishes cleanly —
    exit 0 end to end. Skips where this jaxlib's CPU backend lacks
    cross-process collectives (the test_multihost contract)."""
    import subprocess

    d = str(tmp_path)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [
            sys.executable, "-m", "tpu_dist.cli.launch",
            "--nproc", "2", "--devices_per_proc", "1",
            "--elastic_min_procs", "1", "--elastic_max_restarts", "2",
            "--elastic_backoff", "0.01", "--",
            sys.executable, "-m", "tpu_dist.cli.train",
            "--dataset", "synthetic", "--model", "vit_tiny",
            "--num_classes", "10", "--synthetic_n", "64",
            "--batch_size", "16", "--epochs", "2", "--steps_per_epoch", "2",
            "--eval_every", "0", "--save_every", "1", "--log_every", "50",
            "--seed", "0", "--ckpt_dir", d,
            "--log_file", os.path.join(d, "run.jsonl"),
            "--mid_epoch_save_every", "1",
            "--fault_plan", "sigterm@epoch=1:step=0",
        ],
        env=env, capture_output=True, text=True, timeout=540,
    )
    out = proc.stdout + proc.stderr
    if "Multiprocess computations aren't implemented on the CPU backend" in out:
        pytest.skip("CPU backend lacks multiprocess collectives in this jaxlib")
    assert proc.returncode == 0, out
    assert "elastic: relaunching at world size 2" in out
    recs = [json.loads(l) for l in open(os.path.join(d, "run.jsonl"))]
    resumes = [r for r in recs if r.get("kind") == "resume"]
    # the relaunched rank 0 logged its segment boundary: mid-epoch re-entry
    assert resumes and resumes[-1]["mid_epoch_step"] == 1
    assert resumes[-1]["restarts"] == 1


# -- the full subprocess drill (make elastic-drill) --------------------------


@pytest.mark.slow  # three subprocess training phases (compiles included):
# excluded from the timed tier-1 gate; gates in the CI elastic step
def test_elastic_drill_cli(tmp_path):
    from tpu_dist.elastic.drill import main as drill_main

    assert drill_main([
        "--workdir", str(tmp_path), "--devices", "8", "--shrink_to", "4",
        "--model", "vit_tiny", "--epochs", "2", "--steps_per_epoch", "3",
        "--batch_size", "32", "--kill_step", "1",
    ]) == 0
