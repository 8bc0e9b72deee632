"""Batched, prefetching device feeder — the ``DataLoader``/``ParallelLoader``
role (reference ``distributed.py:71,75``: ``DataLoader(..., num_workers=4,
pin_memory=True, sampler=...)``; torch-xla's ``ParallelLoader`` in the
BASELINE north star).

Differences from torch, by design:

* Datasets at this framework's scope are in-memory numpy arrays, so there
  are no worker *processes*; a single background thread pipelines host-side
  augmentation + H2D placement one batch ahead of the device (the role of
  ``pin_memory`` + workers). When the optional C++ pipeline extension is
  built (``tpu_dist/csrc``), augmentation runs there in native threads.
* The loader emits **globally sharded** ``jax.Array`` batches: one process
  feeds all its local chips (SURVEY §7 design stance), the leading batch
  dim is laid over the mesh's ``data`` axis.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator, Optional, Tuple

import numpy as np
from jax.sharding import Mesh

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.data.sampler import DistributedSampler
from tpu_dist.obs import counters, spans
from tpu_dist.resilience import faults


class LoaderProducerDiedError(RuntimeError):
    """The prefetch producer thread died without finishing the epoch (and
    without surfacing an exception) — e.g. killed at interpreter teardown.
    Raised by the consumer watchdog instead of blocking on ``q.get()``
    forever (docs/resilience.md)."""


class DataLoader:
    def __init__(
        self,
        images: np.ndarray,
        labels: np.ndarray,
        batch_size: int,
        sampler: DistributedSampler,
        mesh: Mesh,
        transform: Optional[Callable[[np.ndarray, np.random.Generator], np.ndarray]] = None,
        eval_transform: Optional[Callable[[np.ndarray], np.ndarray]] = None,
        gather_transform: Optional[Callable] = None,
        seed: int = 0,
        prefetch: int = 2,
        with_mask: bool = False,
        batch_divisor: Optional[int] = None,
        shard_axes=mesh_lib.DATA_AXIS,
        watchdog_timeout: float = 5.0,
    ):
        """``batch_size`` is the PER-PROCESS batch (the reference's manual
        ``global_batch / nprocs`` split, ``distributed.py:67``, happens in
        the trainer). ``with_mask`` adds the sampler's pad mask to each batch
        for exact distributed eval.

        ``gather_transform(images, sel, seed=...)`` is the fused fast path
        (gather + augment + normalize in one pass — the native C++ pipeline,
        ``tpu_dist.data.native.gather_augment``); when given it replaces
        ``transform``/``eval_transform``.

        ``watchdog_timeout`` is the consumer's poll period (seconds) for
        noticing a DEAD producer thread: a slow producer just keeps the
        consumer polling, but a producer that died without its end-of-epoch
        sentinel raises :class:`LoaderProducerDiedError` within one tick
        instead of hanging the epoch forever."""
        n_local = batch_divisor or mesh_lib.local_device_count()
        if batch_size % n_local:
            raise ValueError(
                f"per-process batch {batch_size} must divide over {n_local} "
                f"(local data-parallel) devices"
            )
        self.images = images
        self.labels = labels
        self.batch_size = batch_size
        self.sampler = sampler
        self.mesh = mesh
        self.transform = transform
        self.eval_transform = eval_transform
        self.gather_transform = gather_transform
        self.seed = seed
        self.prefetch = max(1, prefetch)
        self.with_mask = with_mask
        self.shard_axes = shard_axes
        self.watchdog_timeout = watchdog_timeout

    def __len__(self) -> int:
        return len(self.sampler) // self.batch_size if self.sampler.drop_last else -(
            -len(self.sampler) // self.batch_size
        )

    def _host_batches(self, start_batch: int = 0) -> Iterator[Tuple[np.ndarray, ...]]:
        idx = self.sampler.indices()
        mask = self.sampler.pad_mask() if self.with_mask else None
        n = len(idx)
        nb = len(self)
        for b in range(start_batch, nb):
            # Epoch-, rank- AND batch-keyed augmentation stream (init_seeds
            # parity, reference distributed_mp.py:29-39,56).  Keying by the
            # batch index makes batch b's augmentation independent of whether
            # batches 0..b-1 were produced in this process — the property
            # exact mid-epoch resume relies on (resume at step k replays the
            # identical remaining stream).
            rng = np.random.default_rng(
                (self.seed, self.sampler.epoch, self.sampler.shard_id, b)
            )
            sel = idx[b * self.batch_size : (b + 1) * self.batch_size]
            pad = self.batch_size - len(sel)
            bmask = mask[b * self.batch_size : b * self.batch_size + len(sel)] if self.with_mask else None
            if pad:
                # Last partial batch: pad to a static shape with WRAP-AROUND
                # samples from the start of this shard's epoch stream — the
                # same semantics as torch's DistributedSampler padding
                # (distinct examples seen twice, not one example repeated,
                # so the extra gradient weight is spread like torch's).
                # Eval (with_mask=True) masks the tail out exactly either way.
                sel = np.concatenate([sel, np.resize(idx, pad)])
                if bmask is not None:
                    bmask = np.concatenate([bmask, np.zeros(pad, bool)])
            if self.gather_transform is not None:
                imgs = self.gather_transform(
                    self.images, sel, seed=int(rng.integers(0, 2**63))
                )
            else:
                imgs = self.images[sel]
                if self.transform is not None:
                    imgs = self.transform(imgs, rng)
                elif self.eval_transform is not None:
                    imgs = self.eval_transform(imgs)
            out = (imgs, self.labels[sel])
            if self.with_mask:
                out = out + (bmask.astype(np.float32),)
            yield out

    def __iter__(self):
        """Yields device-sharded batches, pipelined one step ahead."""
        return self.iter_from(0)

    def iter_from(self, start_batch: int):
        """Iterate from batch ``start_batch`` of the current epoch — the
        exact-mid-epoch-resume entry point.  Skipped batches are never
        gathered or augmented (index slicing, not produce-and-discard), and
        the per-batch RNG keying in ``_host_batches`` guarantees batch b is
        bit-identical to what an uninterrupted epoch would have produced."""
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        err = []
        stop = threading.Event()
        killed = []  # --fault_plan loader_stall: producer died, no sentinel

        def producer():
            # One clock read per boundary (docs/observability.md): each read
            # closes one region into its always-on counter and, recorder
            # on, its span. (epoch, step) joins a batch's loader/* spans on
            # this thread to its train/* spans on the consumer's. The
            # producer THREAD writes the registry — counters are locked
            # for exactly this.
            epoch = self.sampler.epoch
            try:
                clock = time.perf_counter()
                for b, hb in enumerate(
                    self._host_batches(start_batch), start=start_batch
                ):
                    # the generator's next() ran between the last read and here
                    at = {"epoch": epoch, "step": b}
                    clock = spans.add_timed(
                        "loader/gather", "loader.gather_s", clock, **at
                    )
                    if faults.on_loader_batch(b, self.sampler.epoch) == "die":
                        # simulate a producer killed mid-epoch: exit WITHOUT
                        # the end-of-epoch sentinel (the consumer watchdog
                        # below must notice, not hang)
                        killed.append(b)
                        return
                    batch = mesh_lib.shard_batch(self.mesh, hb, self.shard_axes)
                    counters.inc("loader.h2d_bytes", sum(a.nbytes for a in hb))
                    counters.inc("loader.batches_produced")
                    clock = spans.add_timed("loader/h2d", "loader.h2d_s", clock, **at)
                    # bounded put that notices consumer abandonment (e.g. the
                    # trainer's steps_per_epoch early break) instead of
                    # blocking forever and leaking the thread + device batches
                    while not stop.is_set():
                        try:
                            q.put(batch, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    # time the producer spent blocked on a FULL queue: the
                    # loader outrunning the device (the healthy direction)
                    clock = spans.add_timed(
                        "loader/queue_full", "loader.producer_wait_s", clock, **at
                    )
                    if stop.is_set():
                        return
            except Exception as e:  # surfaced on the consumer side
                err.append(e)
            finally:
                if not stop.is_set() and not killed:
                    q.put(None)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                t_wait = time.perf_counter()
                try:
                    item = q.get(timeout=self.watchdog_timeout)
                except queue.Empty:
                    # polling ticks count as consumer wait too — a slow
                    # producer is exactly what this counter measures
                    counters.add_seconds(
                        "loader.data_wait_s", time.perf_counter() - t_wait
                    )
                    # watchdog: only a DEAD producer with a drained queue is
                    # a failure — nothing can arrive anymore (a live-but-slow
                    # producer just keeps us polling)
                    if not t.is_alive() and q.empty():
                        if err:
                            raise err[0]
                        raise LoaderProducerDiedError(
                            "DataLoader producer thread died without "
                            "finishing the epoch (no sentinel, no error) — "
                            "likely killed mid-epoch; restart the epoch "
                            "instead of waiting on q.get() forever"
                        )
                    continue
                counters.add_seconds(
                    "loader.data_wait_s", time.perf_counter() - t_wait
                )
                if item is None:
                    break
                counters.inc("loader.batches_consumed")
                yield item
        finally:
            stop.set()
            # Abandonment teardown without busy-spinning: ONE drain makes
            # room for any put already in flight; the producer's bounded
            # put (0.1 s timeout + stop check) then either lands it in the
            # freed slot or notices the event — both exit its loop within
            # one timeout tick, so a plain join suffices. (A producer that
            # fills the freed slot re-checks `stop` right after the put and
            # returns — the queue can never refill faster than it exits.)
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join()
            if err:
                raise err[0]
