"""DistributedSampler semantics (reference torch sampler contract,
``distributed.py:70,74,81``)."""

import numpy as np
import pytest

from tpu_dist.data.sampler import DistributedSampler


def test_shards_partition_everything():
    n, shards = 103, 4
    samplers = [DistributedSampler(n, shards, i, shuffle=True, seed=7) for i in range(shards)]
    allidx = np.concatenate([s.indices() for s in samplers])
    # padded total divides evenly; union covers all examples
    assert len(allidx) == samplers[0].total_size == 104
    assert set(allidx.tolist()) == set(range(n))


def test_same_permutation_across_shards():
    a = DistributedSampler(100, 4, 0, seed=3)
    b = DistributedSampler(100, 4, 1, seed=3)
    a.set_epoch(5)
    b.set_epoch(5)
    # interleaved: shard i takes positions i, i+4, ... of ONE global order
    ga, gb = a.indices(), b.indices()
    assert len(set(ga) & set(gb)) == 0


def test_set_epoch_changes_order():
    s = DistributedSampler(100, 2, 0, seed=0)
    s.set_epoch(0)
    e0 = s.indices().copy()
    s.set_epoch(1)
    e1 = s.indices().copy()
    assert not np.array_equal(e0, e1)
    s.set_epoch(0)
    assert np.array_equal(s.indices(), e0)  # deterministic per epoch


def test_no_shuffle_is_identity_order():
    s = DistributedSampler(8, 2, 0, shuffle=False)
    assert s.indices().tolist() == [0, 2, 4, 6]


def test_pad_mask_marks_wraparound():
    # 10 examples over 4 shards -> total 12, two pads at global tail
    samplers = [DistributedSampler(10, 4, i, shuffle=False) for i in range(4)]
    masks = [s.pad_mask() for s in samplers]
    assert sum(int(m.sum()) for m in masks) == 10
    real = sum((s.indices()[m]).tolist().__len__() for s, m in zip(samplers, masks))
    assert real == 10


def test_drop_last():
    s = DistributedSampler(103, 4, 3, drop_last=True)
    assert len(s) == 25
    assert s.pad_mask().all()


def test_bad_shard_id():
    with pytest.raises(ValueError):
        DistributedSampler(10, 2, 2)


@pytest.mark.parametrize("drop_last", [False, True])
@pytest.mark.parametrize("shuffle", [True, False])
def test_indices_of_a_given_epoch_are_pure(shuffle, drop_last):
    """``indices(epoch)`` is what ``set_epoch(epoch); indices()`` gives, and
    leaves the sampler as it was: the loader's look-ahead asks for the next
    epoch's order while the current one is in flight."""
    s = DistributedSampler(103, 4, 2, shuffle=shuffle, seed=7, drop_last=drop_last)
    s.set_epoch(3)
    here = s.indices().copy()
    ahead = s.indices(4)
    assert s.epoch == 3 and np.array_equal(s.indices(), here)
    assert np.array_equal(s.indices(3), here)
    s.set_epoch(4)
    assert np.array_equal(s.indices(), ahead)
    assert np.array_equal(ahead, here) == (not shuffle)
