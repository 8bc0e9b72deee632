"""Batched, prefetching device feeder — the ``DataLoader``/``ParallelLoader``
role (reference ``distributed.py:71,75``: ``DataLoader(..., num_workers=4,
pin_memory=True, sampler=...)``; torch-xla's ``ParallelLoader`` in the
BASELINE north star).

Differences from torch, by design:

* Datasets at this framework's scope are in-memory numpy arrays, so there
  are no worker *processes*; one background thread per epoch pipelines
  host-side augmentation + H2D placement up to ``prefetch`` batches ahead
  of the device (the role of ``pin_memory`` + workers). When the optional
  C++ pipeline extension is built (``tpu_dist/csrc``), augmentation runs
  there in native threads.
* The pipeline stays full across the epoch boundary: a producer that has
  queued its epoch's last batch goes on to gather and place exactly ONE
  more, batch 0 of the next epoch, and parks it on the loader under the
  key that determines its content. The next ``iter_from`` yields it first
  if the key still holds (the sampler moved on by one epoch, no offset, no
  mid-epoch start) and drops it otherwise, so the first batch's gather and
  copy overlap the chip draining the epoch before it. Batches are keyed by
  ``(seed, epoch, shard, index)``, so a parked batch is bit-identical to
  the one a cold start would make. One batch, not more: each parked batch
  is device memory held beside the running step's.
* The loader emits **globally sharded** ``jax.Array`` batches: one process
  feeds all its local chips (SURVEY §7 design stance), the leading batch
  dim is laid over the mesh's ``data`` axis.
"""

from __future__ import annotations

import atexit
import queue
import threading
import time
import weakref
from typing import Callable, Iterator, Optional, Tuple

import numpy as np
from jax.sharding import Mesh

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.data.sampler import DistributedSampler
from tpu_dist.obs import counters, spans
from tpu_dist.resilience import faults


# Producer threads that ended their epoch normally and may still be placing
# the next epoch's first batch. They are daemons (an abandoned one must not
# hold the interpreter), so the exit joins them: a thread inside device_put
# while the runtime is torn down is a crash at exit.
_LOOKING_AHEAD: "weakref.WeakSet[threading.Thread]" = weakref.WeakSet()


@atexit.register
def _join_look_aheads() -> None:
    for t in list(_LOOKING_AHEAD):
        t.join(timeout=10.0)


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
        # the look-ahead across the epoch boundary (module docstring): the
        # last producer that ended its epoch normally, and what it parked
        self._ahead_thread: Optional[threading.Thread] = None
        self._parked: Optional[Tuple[tuple, tuple]] = None  # (key, device batch)

    def __len__(self) -> int:
        return len(self.sampler) // self.batch_size if self.sampler.drop_last else -(
            -len(self.sampler) // self.batch_size
        )

    def _host_batches(
        self, start_batch: int, epoch: int
    ) -> Iterator[Tuple[np.ndarray, ...]]:
        """Host batches ``start_batch..`` of ``epoch``. The epoch is an
        argument, never read from the sampler in here: the look-ahead asks
        for epoch e+1 while e is in flight, from the producer's thread."""
        idx = self.sampler.indices(epoch)
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
                (self.seed, epoch, self.sampler.shard_id, b)
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
        """Yields device-sharded batches, pipelined ``prefetch`` ahead."""
        return self.iter_from(0)

    def _place(self, hb, clock: float, at: dict):
        """Host batch -> globally sharded device batch; closes ``loader/h2d``."""
        batch = mesh_lib.shard_batch(self.mesh, hb, self.shard_axes)
        counters.inc("loader.h2d_bytes", sum(a.nbytes for a in hb))
        counters.inc("loader.batches_produced")
        return batch, spans.add_timed("loader/h2d", "loader.h2d_s", clock, **at)

    def _ahead_key(self, epoch: int) -> tuple:
        """What determines batch 0 of ``epoch``, as far as the loader can
        observe it: a parked batch is used only under an equal key."""
        s = self.sampler
        return (
            epoch, s.offset, s.seed, s.shuffle, s.num_examples, s.num_shards,
            s.shard_id, s.drop_last, self.seed, self.batch_size, self.with_mask,
        )

    def _look_ahead(self, epoch: int) -> None:
        """Producer thread, after its epoch's sentinel: gather and place
        batch 0 of ``epoch`` and park it. Nothing where the next epoch
        cannot be known from here: a sampler that does not shuffle (the
        eval loaders iterate one epoch over and over), an epoch shortened
        by ``set_offset``, an armed ``--fault_plan`` (``loader_stall`` and
        the watchdog keep their exact batch coordinates)."""
        s = self.sampler
        if not s.shuffle or s.offset or faults.active() is not None:
            return
        key = self._ahead_key(epoch)
        at = {"epoch": epoch, "step": 0}
        clock = time.perf_counter()  # the sentinel's put is no gather time
        hb = next(self._host_batches(0, epoch), None)
        if hb is None:
            return
        clock = spans.add_timed("loader/gather", "loader.gather_s", clock, **at)
        # A fault in here ends the thread under threading.excepthook with
        # nothing parked: the next epoch starts cold and meets the same
        # fault on its own producer, where the consumer sees it.
        self._parked = (key, self._place(hb, clock, at)[0])

    def _take_ahead(self, epoch: int, start_batch: int):
        """Consumer side, entering an epoch: the parked batch if its key is
        this epoch's batch 0, else None (and the parked one is dropped)."""
        t, self._ahead_thread = self._ahead_thread, None
        if t is not None:
            # a look-ahead still in flight: waiting for it is data wait
            t_wait = time.perf_counter()
            t.join()
            counters.add_seconds("loader.data_wait_s", time.perf_counter() - t_wait)
        parked, self._parked = self._parked, None
        if parked is None:
            return None
        if (
            start_batch == 0
            and faults.active() is None
            and parked[0] == self._ahead_key(epoch)
        ):
            counters.inc("loader.ahead_hits")
            return parked[1]
        counters.inc("loader.ahead_discards")
        return None

    def iter_from(self, start_batch: int):
        """Iterate from batch ``start_batch`` of the current epoch — the
        exact-mid-epoch-resume entry point.  Skipped batches are never
        gathered or augmented (index slicing, not produce-and-discard), and
        the per-batch RNG keying in ``_host_batches`` guarantees batch b is
        bit-identical to what an uninterrupted epoch would have produced.
        Batch 0 comes from the previous epoch's look-ahead where its key
        holds (module docstring); the producer then starts at batch 1."""
        epoch = self.sampler.epoch  # read once: the epoch this iterator serves
        parked = self._take_ahead(epoch, start_batch)
        first = start_batch + (parked is not None)
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
            try:
                clock = time.perf_counter()
                for b, hb in enumerate(
                    self._host_batches(first, epoch), start=first
                ):
                    # the generator's next() ran between the last read and here
                    at = {"epoch": epoch, "step": b}
                    clock = spans.add_timed(
                        "loader/gather", "loader.gather_s", clock, **at
                    )
                    if faults.on_loader_batch(b, epoch) == "die":
                        # simulate a producer killed mid-epoch: exit WITHOUT
                        # the end-of-epoch sentinel (the consumer watchdog
                        # below must notice, not hang)
                        killed.append(b)
                        return
                    batch, clock = self._place(hb, clock, at)
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
            # The sentinel went first, so the consumer's last next() is not
            # delayed; a consumer that left early (stop) gets no look-ahead.
            if not (err or killed or stop.is_set()):
                self._look_ahead(epoch + 1)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        finished = False  # the consumer saw the end-of-epoch sentinel
        try:
            if parked is not None:
                counters.inc("loader.batches_consumed")
                yield parked
                parked = None  # this frame lives all epoch: hold no batch in it
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
                    finished = True
                    break
                counters.inc("loader.batches_consumed")
                yield item
        finally:
            if finished:
                # Normal end: the queue is empty and the producer is past
                # its sentinel, gathering the next epoch's first batch (or
                # done). Neither stopped nor joined here: the look-ahead
                # overlaps the chip's drain, and the next iter_from (or the
                # interpreter's exit) joins it.
                self._ahead_thread = t
                _LOOKING_AHEAD.add(t)
            else:
                stop.set()
                # Abandonment teardown without busy-spinning: ONE drain
                # makes room for any put already in flight; the producer's
                # bounded put (0.1 s timeout + stop check) then either lands
                # it in the freed slot or notices the event — both exit its
                # loop within one timeout tick, so a plain join suffices. (A
                # producer that fills the freed slot re-checks `stop` right
                # after the put and returns — the queue can never refill
                # faster than it exits.)
                while True:
                    try:
                        q.get_nowait()
                    except queue.Empty:
                        break
                t.join()
            if err:
                raise err[0]
