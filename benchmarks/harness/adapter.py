"""The seam between the benchmark and the program: the file that knows
``Trainer``'s insides, as far as any run through ``Trainer`` needs them.

What is here holds for every cell: ``TrainConfig`` from the cell's files, the
mesh, ``Trainer``, batch 0 through ``trainer.train_loader.iter_from``, one
update through ``trainer.train_step``, epochs through ``trainer.train_epoch``,
the traced run's wrappers, the window's program and the memory statistics.
What a sample is (the arrays made from ``--seed``, the sampler and loader
built over them, how the program counts what it consumed) is the cell's data
kind, ``benchmarks/data/<kind>.py``: the adapter calls its ``make`` and
``attach`` and knows inputs and targets only by their leading axis.

What is left of the seam: ``Trainer`` cannot take its data yet (it builds
every data set itself, ``trainer.py:546-572``), so a ``Trainer`` is
constructed on a small set of its own and the kind's ``attach`` replaces
``train_data``, ``train_sampler``, ``train_loader`` (and the fused runner's
device-resident copy) after construction. That it takes a swap at all is a
finding: see ``PERF.md``, Open questions.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmarks.harness.manifest import Cell, data_module, train_config_fields


class Adapter:
    def __init__(self, cell: Cell, seed: int, devices) -> None:
        from tpu_dist.comm import mesh as mesh_lib  # noqa: PLC0415
        from tpu_dist.config.config import TrainConfig  # noqa: PLC0415
        from tpu_dist.train.trainer import Trainer  # noqa: PLC0415

        self.cell = cell
        self.kind = data_module(cell)
        self.timing: Dict[str, float] = {}
        t = time.perf_counter()
        inputs, targets = self.kind.make(cell, seed)
        self.timing["data_generation_s"] = time.perf_counter() - t

        t = time.perf_counter()
        cfg = TrainConfig(**train_config_fields(cell, seed))
        mesh = mesh_lib.device_mesh(
            [cell.chips], [mesh_lib.DATA_AXIS], list(devices)[: cell.chips]
        )
        tr = Trainer(cfg, mesh=mesh)
        cfg = tr.cfg
        self.kind.attach(tr, cell, seed, inputs, targets)
        self.timing["trainer_build_s"] = time.perf_counter() - t
        self.trainer = tr
        self.cfg = cfg
        self.devices = list(devices)[: cell.chips]
        self._steps_seen: List[Any] = []  # per-step loss handles (traced run)
        self._spans: List[Tuple[str, float, float]] = []  # host clock (traced run)

    # -- sizes -----------------------------------------------------------------

    @property
    def full_epoch_steps(self) -> int:
        if self.cell.fused:
            from tpu_dist.train.epoch import fused_steps_per_epoch  # noqa: PLC0415

            return fused_steps_per_epoch(self.cell.n_train, self.cell.global_batch)
        return len(self.trainer.train_loader)

    # -- the reference comparison's inputs --------------------------------------

    def first_batch(self, n: Optional[int] = None) -> Tuple[Any, Any]:
        """Batch 0 of epoch 0 exactly as ``train_epoch(0)`` will consume it
        (the loader keys every batch by seed, epoch, shard and index), cut to
        its first ``n`` samples if asked."""
        from tpu_dist.comm import mesh as mesh_lib  # noqa: PLC0415

        tr = self.trainer
        tr.train_sampler.set_epoch(0)
        it = tr.train_loader.iter_from(0)
        try:
            inputs, targets = next(it)
        finally:
            it.close()
        if n is not None and n < targets.shape[0]:
            host = (np.asarray(inputs)[:n], np.asarray(targets)[:n])
            inputs, targets = mesh_lib.shard_batch(tr.mesh, host)
        return inputs, targets

    def first_update(self, inputs, targets) -> Dict[str, Any]:
        """One optimizer step of the program on ``(inputs, targets)`` from the
        seeded initial state: its loss, and the parameters before and after
        as host arrays. A whole first batch goes through ``trainer.train_step``
        itself (so no extra shape compiles, and the trainer is one step on);
        a cut batch goes through a second instance of the same step builder on
        a copy of the state, because the trainer's own jit must see one shape."""
        import jax  # noqa: PLC0415

        tr = self.trainer
        lr = tr._lr(0)
        before = jax.device_get(tr.state.params)
        whole = targets.shape[0] == self.cell.global_batch
        if whole:
            tr.state, metrics = tr.train_step(tr.state, inputs, targets, lr)
            after = tr.state.params
        else:
            import jax.numpy as jnp  # noqa: PLC0415

            step = tr._build_train_step(
                self.cfg, jnp.bfloat16 if self.cfg.bf16 else jnp.float32
            )
            # the step donates its state: give it a copy made on the device
            copy = jax.jit(lambda st: jax.tree_util.tree_map(jnp.copy, st))(tr.state)
            new, metrics = step(copy, inputs, targets, lr)
            after = new.params
        return {
            "loss": float(jax.device_get(metrics["loss"])),
            "lr": float(lr), "before": before, "after": jax.device_get(after),
            "through": "trainer.train_step" if whole else "a second instance of the step",
        }

    # -- epochs ----------------------------------------------------------------

    def run_epoch(self, epoch: int, steps: Optional[int] = None) -> Dict[str, Any]:
        """``trainer.train_epoch(epoch)``, capped at ``steps`` (streamed
        loader only), and what it reports in the benchmark's own words."""
        tr = self.trainer
        if steps is not None and self.cell.fused:
            raise ValueError("the fused runner cannot cut an epoch")
        tr.cfg.steps_per_epoch = steps
        t = time.perf_counter()
        out = tr.train_epoch(epoch)
        wall = time.perf_counter() - t
        if self.cell.fused:
            n_steps = self.full_epoch_steps
            counted = int(tr._fused_data[0].shape[0])
            counted -= counted % self.cell.global_batch  # the dropped ragged tail
        else:
            n_steps = int(out["steps"])
            counted = int(self.kind.samples_counted(out))
        return {
            "epoch": epoch, "steps": n_steps, "wall_s": wall,
            "samples": n_steps * self.cell.global_batch,
            "samples_counted_by_program": counted,
            "loss": float(out["loss"]),
            "data_wait_s": float(out.get("data_wait_s", 0.0)),
            "dispatch_s": float(out.get("dispatch_s", 0.0)),
            "host_fetch_s": float(out.get("host_fetch_s", 0.0)),
        }

    # -- spans from the benchmark's own files (traced run only) ----------------

    def instrument(self) -> None:
        """Wrap ``trainer.train_step`` and the loader's iterator to record,
        on ``time.perf_counter``, the spans ``bench/dispatch`` and
        ``bench/data_wait``, and keep each step's loss handle (never fetched
        inside the window). Not ``TraceAnnotation``s: the profiler's host
        tracer costs this loop half its rate (``harness/trace.py``), so the
        spans are put on the trace's clock afterwards. The program's own
        ``train/dispatch`` and ``train/data_wait`` are ``add_event`` records
        on the same clock that nothing outside it can read per step."""
        tr, spans, seen = self.trainer, self._spans, self._steps_seen
        inner = tr.train_step

        class _Step:
            def __call__(self, *args):
                t = time.perf_counter()
                new_state, metrics = inner(*args)
                spans.append(("bench/dispatch", t, time.perf_counter()))
                seen.append(metrics["loss"])
                return new_state, metrics

            def __getattr__(self, name):  # .lower, ._cache_size, ...
                return getattr(inner, name)

        tr.train_step = _Step()
        loader = tr.train_loader
        iter_from = loader.iter_from

        def timed_iter_from(start_batch: int):
            it = iter_from(start_batch)
            try:
                while True:
                    t = time.perf_counter()
                    try:
                        item = next(it)
                    except StopIteration:
                        return
                    spans.append(("bench/data_wait", t, time.perf_counter()))
                    yield item
            finally:
                it.close()

        loader.iter_from = timed_iter_from

    def spans_since(self, t0: float) -> List[Tuple[str, float, float]]:
        """The recorded spans that began at or after ``t0``."""
        return [sp for sp in self._spans if sp[1] >= t0]

    def laps_s(self, breaks: Tuple[float, ...] = ()) -> List[float]:
        """Seconds between consecutive dispatches since ``instrument``; a
        lap that holds one of ``breaks`` (the profiler stopping) is no lap."""
        starts = [a for n, a, _ in self._spans if n == "bench/dispatch"]
        return [b - a for a, b in zip(starts, starts[1:])
                if not any(a <= t < b for t in breaks)]

    def program(self) -> Dict[str, Any]:
        """The window's program as the compiler leaves it: an AOT compile of
        the same jitted step at the same shapes (a load from the persistent
        cache once the warm-up has run), for what the trace does not tell:
        which fused computations hold a convolution or a dot, and which
        Pallas kernels a matrix product. Its
        ``memory_analysis`` goes on an earlier line beside the runtime's own
        count."""
        from benchmarks.harness import trace as trace_lib  # noqa: PLC0415

        tr = self.trainer
        if self.cell.fused:
            jitted, args = tr._fused_runner, (tr.state, *tr._fused_data, tr._lr(0), 0)
        else:
            jitted, args = tr.train_step, (tr.state, *self.first_batch(), tr._lr(0))
        compiled = jitted.lower(*args).compile()
        mem, text = compiled.memory_analysis(), compiled.as_text()
        return {
            "temp_bytes": int(mem.temp_size_in_bytes),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "matmul_computations": trace_lib.matmul_computations(text),
            "mosaic_kernels": trace_lib.mosaic_kernels(text),
        }

    def loss_at(self, index: int) -> Optional[float]:
        """Loss of the ``index``-th step dispatched since ``instrument``."""
        import jax  # noqa: PLC0415

        if index >= len(self._steps_seen):
            return None
        return float(jax.device_get(self._steps_seen[index]))

    def memory_stats(self) -> List[Dict[str, Any]]:
        return [dict(d.memory_stats() or {}) for d in self.devices]

    def peak_bytes(self) -> int:
        """Largest peak over the cell's chips, as the runtime counts it: the
        allocator's buffers (``peak_bytes_in_use``: state, batches) plus what
        it reserves for the executables' temporaries (``peak_bytes_reserved``),
        which on this runtime is not part of the first (PR 22: ViT-B/16 at
        batch 128 shows 2.9 GB in use beside 9.3 GB reserved, and XLA's
        ``memory_analysis`` gives 8.69 GiB of temporaries). 0 where the
        backend keeps no statistics, as the CPU."""
        return max(
            int(s.get("peak_bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0))
            for s in self.memory_stats()
        )
