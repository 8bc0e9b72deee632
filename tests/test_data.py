"""Data pipeline: transforms, loader sharding/prefetch, CIFAR reader."""

import numpy as np
import pytest

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.data import DataLoader, DistributedSampler, synthetic_cifar, transforms
from tpu_dist.data.cifar import load_cifar100


def test_normalize_matches_reference_constants():
    x = np.full((2, 32, 32, 3), 128, np.uint8)
    y = transforms.normalize(x)
    expect = (128 / 255.0 - transforms.CIFAR100_MEAN) / transforms.CIFAR100_STD
    np.testing.assert_allclose(y[0, 0, 0], expect, rtol=1e-6)


def test_random_crop_shape_and_determinism():
    x = np.random.default_rng(0).integers(0, 255, (8, 32, 32, 3)).astype(np.uint8)
    a = transforms.random_crop_batch(x, np.random.default_rng(5))
    b = transforms.random_crop_batch(x, np.random.default_rng(5))
    c = transforms.random_crop_batch(x, np.random.default_rng(6))
    assert a.shape == x.shape
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_crop_windows_come_from_padded_image():
    x = np.ones((1, 8, 8, 3), np.uint8) * 7
    out = transforms.random_crop_batch(x, np.random.default_rng(0), padding=4)
    # every output pixel is either original (7) or zero padding
    assert set(np.unique(out)) <= {0, 7}


def test_loader_yields_sharded_batches():
    mesh = mesh_lib.data_parallel_mesh()
    imgs, lbls = synthetic_cifar(200, 10)
    sampler = DistributedSampler(200, 1, 0, seed=0)
    dl = DataLoader(imgs, lbls, 40, sampler, mesh,
                    transform=transforms.train_augment, seed=0)
    batches = list(dl)
    assert len(batches) == len(dl) == 5
    x, y = batches[0]
    assert x.shape == (40, 32, 32, 3) and y.shape == (40,)
    assert x.dtype == np.float32
    assert len(x.sharding.device_set) == 8  # spread over the mesh


def test_loader_epoch_reshuffle_changes_batches():
    mesh = mesh_lib.data_parallel_mesh()
    imgs, lbls = synthetic_cifar(64, 10)
    sampler = DistributedSampler(64, 1, 0, seed=0)
    dl = DataLoader(imgs, lbls, 64, sampler, mesh, seed=0)
    sampler.set_epoch(0)
    y0 = np.asarray(next(iter(dl))[1])
    sampler.set_epoch(1)
    y1 = np.asarray(next(iter(dl))[1])
    assert not np.array_equal(y0, y1)


def test_loader_early_break_no_thread_leak():
    import threading

    mesh = mesh_lib.data_parallel_mesh()
    imgs, lbls = synthetic_cifar(512, 10)
    dl = DataLoader(imgs, lbls, 32, DistributedSampler(512, 1, 0), mesh)
    before = threading.active_count()
    for _ in range(4):
        for i, _b in enumerate(dl):
            if i >= 1:
                break
    import time

    time.sleep(0.3)
    assert threading.active_count() <= before + 1


def test_indivisible_batch_rejected():
    mesh = mesh_lib.data_parallel_mesh()
    imgs, lbls = synthetic_cifar(64, 10)
    with pytest.raises(ValueError, match="divide"):
        DataLoader(imgs, lbls, 30, DistributedSampler(64, 1, 0), mesh)


def test_cifar_missing_data_is_loud(tmp_path):
    with pytest.raises(FileNotFoundError, match="CIFAR-100 not found"):
        load_cifar100(str(tmp_path))


def test_cifar100_reads_pickle_layout(tmp_path):
    import pickle

    root = tmp_path / "cifar-100-python"
    root.mkdir()
    rng = np.random.default_rng(0)
    raw = rng.integers(0, 256, (6, 3072), dtype=np.int64).astype(np.uint8)
    with open(root / "train", "wb") as f:
        pickle.dump({"data": raw, "fine_labels": list(range(6))}, f)
    imgs, lbls = load_cifar100(str(tmp_path), train=True)
    assert imgs.shape == (6, 32, 32, 3) and lbls.tolist() == [0, 1, 2, 3, 4, 5]
    # channel-major 3072 -> NHWC round trip
    np.testing.assert_array_equal(
        imgs[0], raw[0].reshape(3, 32, 32).transpose(1, 2, 0)
    )


def test_cifar10_reads_batch_layout(tmp_path):
    import pickle

    from tpu_dist.data.cifar import load_cifar10

    root = tmp_path / "cifar-10-batches-py"
    root.mkdir()
    rng = np.random.default_rng(1)
    for i in range(1, 6):
        raw = rng.integers(0, 256, (4, 3072), dtype=np.int64).astype(np.uint8)
        with open(root / f"data_batch_{i}", "wb") as f:
            pickle.dump({"data": raw, "labels": [i] * 4}, f)
    imgs, lbls = load_cifar10(str(tmp_path), train=True)
    assert imgs.shape == (20, 32, 32, 3)
    assert lbls.tolist() == sum(([i] * 4 for i in range(1, 6)), [])
    with pytest.raises(FileNotFoundError, match="CIFAR-10 not found"):
        load_cifar10(str(tmp_path / "nope"))


def test_train_pad_wraps_distinct_samples():
    """The last partial train batch pads with wrap-around samples from the
    epoch stream (torch DistributedSampler semantics), not one repeated
    example (which would give a single image pad× gradient weight)."""
    mesh = mesh_lib.data_parallel_mesh()
    # 72 examples, batch 16 -> last batch has 8 real + 8 pad
    imgs, lbls = synthetic_cifar(72, 10)
    lbls = np.arange(72).astype(np.int32) % 10  # identifiable labels
    sampler = DistributedSampler(72, 1, 0, seed=0, shuffle=False)
    dl = DataLoader(imgs, lbls, 16, sampler, mesh, seed=0, batch_divisor=8)
    batches = [np.asarray(y) for _, y in dl]
    last = batches[-1]
    # tail = first 8 of the epoch stream (wrap-around), not last[7] repeated
    np.testing.assert_array_equal(last[8:], batches[0][:8])
    assert not np.all(last[8:] == last[7])


# -- the look-ahead across the epoch boundary --------------------------------
# A producer that queued its epoch's last batch goes on to place batch 0 of
# the next epoch and parks it; the next iter_from uses it only under an equal
# key. Every script below runs once on ONE loader (look-ahead at work) and
# once on a fresh loader per iteration (every epoch cold, the behaviour before
# the look-ahead): the streams must be equal byte for byte, and the counters
# must say what the mechanism did.

N, NB = 240, 5  # examples, batches an epoch (per shard)
NEVER = "loader_stall@batch=99"  # an armed plan whose clause never matches


def _loader_kinds():
    import functools

    from tpu_dist.data import native

    stats = dict(mean=transforms.CIFAR100_MEAN, std=transforms.CIFAR100_STD)
    return {
        "transform": dict(transform=transforms.train_augment),
        "gather": dict(gather_transform=functools.partial(
            native.gather_augment, train=True, **stats)),
        "two_shards_drop_last": dict(transform=transforms.train_augment,
                                     shards=(2, 1), drop_last=True),
        "with_mask": dict(transform=transforms.train_augment, with_mask=True),
        "eval": dict(eval_transform=transforms.normalize, shuffle=False,
                     with_mask=True),
    }


def _make(kind, data, sampler=None):
    kw = dict(_loader_kinds()[kind])
    shards, shard_id = kw.pop("shards", (1, 0))
    shuffle, drop_last = kw.pop("shuffle", True), kw.pop("drop_last", False)
    if sampler is None:
        sampler = DistributedSampler(
            N, shards, shard_id, shuffle=shuffle, seed=3, drop_last=drop_last
        )
    loader = DataLoader(*data, N // shards // NB, sampler,
                        mesh_lib.data_parallel_mesh(), seed=3, **kw)
    return sampler, loader


def _quiesce(baseline, timeout=20.0):
    """Wait until every producer thread has ended (look-aheads included)."""
    import threading
    import time

    deadline = time.monotonic() + timeout
    while threading.active_count() > baseline and time.monotonic() < deadline:
        time.sleep(0.005)
    assert threading.active_count() <= baseline, "a producer thread is still alive"


def _play(loader, sampler, op, baseline):
    """One iteration as ``op`` says; the batches as bytes."""
    from tpu_dist.resilience import faults

    if "arm" in op:
        faults.install(NEVER) if op["arm"] else faults.clear()
    if "epoch" in op:
        sampler.set_epoch(op["epoch"])
    if "offset" in op:
        sampler.set_offset(op["offset"])
    out = []
    it = loader.iter_from(op.get("start", 0))
    try:
        for i, batch in enumerate(it):
            out.append(tuple(np.asarray(a).tobytes() for a in batch))
            if i + 1 == op.get("take"):
                break
    finally:
        it.close()
    _quiesce(baseline)
    return out


# name: (loader kind, script, hits, discards, batches produced - consumed);
# None = not pinned (how far a producer ran ahead of an early exit is timing)
FULL = [dict(epoch=e) for e in range(3)]
LOOK_AHEAD_CASES = {
    # three epochs, the two boundaries hit, the third look-ahead stays parked
    **{f"consecutive-{k}": (k, FULL, 2, 0, 1)
       for k in ("transform", "gather", "two_shards_drop_last", "with_mask")},
    # the misses: each yields exactly the cold stream
    "mid_epoch_start": ("transform", [dict(epoch=0), dict(epoch=1, start=2)], 0, 1, 2),
    "offset_on_the_next_epoch": ("transform", [dict(epoch=0), dict(epoch=1, offset=48)], 0, 1, 1),
    "offset_on_this_epoch": ("transform", [dict(epoch=0, offset=48), dict(epoch=1)], 0, 0, 1),
    "epoch_not_advancing": ("transform", [dict(epoch=0), dict()], 0, 1, 2),
    "repeated_epoch": ("transform", [dict(epoch=0), dict(epoch=1), dict(epoch=1)], 1, 1, 2),
    "epoch_skipped": ("transform", [dict(epoch=0), dict(epoch=2)], 0, 1, 2),
    "early_close": ("transform", [dict(epoch=0, take=1), dict(epoch=1)], 0, 0, None),
    # every batch taken but not the sentinel: an early exit all the same. The
    # producer may have been past its sentinel already; what it then parks is
    # keyed like any other, so the stream is the cold one either way
    "exit_before_the_sentinel": ("transform", [dict(epoch=0, take=NB), dict(epoch=1)], None, 0, None),
    "fault_plan_armed": ("transform", [dict(epoch=0, arm=True), dict(epoch=1)], 0, 0, 0),
    "fault_plan_armed_at_the_boundary": ("transform", [dict(epoch=0), dict(epoch=1, arm=True)], 0, 1, 1),
    # validate() iterates one epoch over and over: nothing gathered ahead
    "eval_loader": ("eval", [dict(), dict(), dict()], 0, 0, 0),
}


@pytest.mark.parametrize("case", sorted(LOOK_AHEAD_CASES))
def test_look_ahead_stream_is_the_cold_stream(case):
    import threading

    from tpu_dist.obs import counters
    from tpu_dist.resilience import faults

    kind, script, hits, discards, parked = LOOK_AHEAD_CASES[case]
    data = synthetic_cifar(N, 10)
    baseline = threading.active_count()
    before = counters.snapshot()
    try:
        sampler, loader = _make(kind, data)
        warm = [_play(loader, sampler, op, baseline) for op in script]
        got = counters.delta(before, counters.snapshot())
        faults.clear()
        sampler = None
        cold = []
        for op in script:  # one sampler through the script, a fresh loader each time
            sampler, loader = _make(kind, data, sampler)
            cold.append(_play(loader, sampler, op, baseline))
    finally:
        faults.clear()
    assert [len(e) for e in warm] == [len(e) for e in cold]
    assert warm == cold
    assert all(len(e) == op.get("take", NB - op.get("start", 0) - (op.get("offset", 0) > 0))
               for e, op in zip(warm, script))
    if hits is not None:
        assert got.get("loader.ahead_hits", 0) == hits
    assert got.get("loader.ahead_discards", 0) == discards
    if parked is not None:
        # no thread outlives its loader's last iterator by more than one
        # batch: what was produced and not consumed is the discards plus at
        # most the one batch still parked
        extra = got["loader.batches_produced"] - got["loader.batches_consumed"]
        assert extra == parked and extra - discards in (0, 1)


def test_look_ahead_spans_carry_the_next_epochs_coordinates():
    import threading

    from tpu_dist.obs import counters, spans

    baseline = threading.active_count()
    sampler, loader = _make("transform", synthetic_cifar(N, 10))
    spans.disable()
    spans.drain()
    spans.enable()
    try:
        before = counters.snapshot()
        _play(loader, sampler, dict(epoch=4), baseline)
        evts = spans.events()
        got = counters.delta(before, counters.snapshot())
    finally:
        spans.disable()
        spans.drain()
    for name in ("loader/gather", "loader/h2d"):
        at = [(e["args"]["epoch"], e["args"]["step"]) for e in evts if e["name"] == name]
        assert at == [(4, b) for b in range(NB)] + [(5, 0)], name
    # one producer thread made all six, the look-ahead after its last batch
    assert len({e["tid"] for e in evts if e["name"].startswith("loader/")}) == 1
    assert got["loader.batches_produced"] == NB + 1
    per_batch = N // NB * (32 * 32 * 3 * 4 + 4)
    assert got["loader.h2d_bytes"] == (NB + 1) * per_batch


def test_trainer_losses_with_look_ahead_equal_cold_epochs(monkeypatch):
    """Three epochs through ``Trainer.train_epoch``: every boundary a hit,
    and every step's loss the one a loader without the look-ahead gives."""
    import jax

    from tests.helpers import TinyConvNet
    from tpu_dist.config import TrainConfig
    from tpu_dist.obs import counters
    from tpu_dist.train import trainer as trainer_mod

    trainer_mod.register_model(
        "tiny_look_ahead", lambda num_classes=10: TinyConvNet(num_classes)
    )

    def losses():
        cfg = TrainConfig(
            dataset="synthetic", model="tiny_look_ahead", num_classes=10,
            batch_size=64, epochs=3, eval_every=0, synthetic_n=320,
            log_every=100, seed=0,
        )
        tr = trainer_mod.Trainer(cfg)
        inner, seen = tr.train_step, []

        def step(*args):
            state, metrics = inner(*args)
            seen.append(metrics["loss"])
            return state, metrics

        tr.train_step = step
        before = counters.snapshot()
        for epoch in range(3):
            assert tr.train_epoch(epoch)["steps"] == 5
        got = counters.delta(before, counters.snapshot())
        return [float(x) for x in jax.device_get(seen)], got

    warm, got = losses()
    assert got["loader.ahead_hits"] == 2 and "loader.ahead_discards" not in got
    monkeypatch.setattr(DataLoader, "_look_ahead", lambda self, epoch: None)
    cold, got = losses()
    assert "loader.ahead_hits" not in got
    assert len(warm) == 15 and warm == cold
