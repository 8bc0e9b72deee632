"""One run of one cell: set-up, the measured window, the result line.

Set-up is everything before the window's first step: the data set, the
``Trainer``, the program's first update (kept for the reference comparison),
the warm-up epochs (compiles, the program's cost/memory AOT compiles, the C++
build on a first run). The window
is whole calls of ``trainer.train_epoch`` (what ``fit`` calls) until
``--seconds`` have passed; the last epoch of a streamed cell is sized from the
measured rate, a fused cell ends at the first epoch boundary after
``--seconds``. With ``--trace 1`` the window's first epoch runs under the
profiler, capped at the traffic file's ``trace_steps``. After the window come
the float32 reference and the comparison that decides ``correct`` and, in a
traced run, an AOT compile of the window's program for the ops that hold
its matrix products.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.harness import manifest as manifest_lib
from benchmarks.harness.manifest import Cell, RefusedError

GIB = float(1 << 30)
WARMUP_STEPS = 8
TRACE_STEPS = 12


def _say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def check_devices(cell: Cell):
    """The chips this run may use, or RefusedError: no fallback to the CPU."""
    import jax  # noqa: PLC0415

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise RefusedError(f"JAX found no accelerator: {e}") from e
    if devices[0].platform != "tpu":
        raise RefusedError(
            f"JAX runs on {devices[0].platform!r}, not on a TPU "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    if len(devices) < cell.chips:
        raise RefusedError(f"cell {cell.name} needs {cell.chips} chips, JAX found {len(devices)}")
    return devices


class CompileCounter:
    """Counts programs lowered or compiled while armed (``jax.monitoring``
    fires a duration event for each, also when the persistent cache serves
    the executable): inside the window there must be none."""

    def __init__(self) -> None:
        from jax import monitoring  # noqa: PLC0415

        self.armed = False
        self.events: List[str] = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.armed and ("backend_compile" in event or "jaxpr_to_mlir" in event):
            self.events.append(event)


# (number, its limit) of the reference check, as ``reference.compare`` names them
COMPARED = (("loss_rel_err", "loss_rel_tol"), ("sign_agreement", "sign_agreement_min"),
            ("grad_rel_l2_err", "grad_rel_l2_tol"), ("worst_leaf_cosine", "leaf_cosine_min"))


def compared(verdict: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """The numbers the reference check compared, each beside its limit: the
    result line's last key, and ``run.py`` prints them as the last lines of
    standard error."""
    return {v: {"value": float(verdict[v]), "limit": float(verdict[lim])}
            for v, lim in COMPARED if v in verdict}


def _kernels_seen(kernels: Dict[str, Optional[bool]]) -> str:
    """What the program's text said of its Pallas kernels, by stem."""
    if not kernels:
        return "; no Pallas kernel"
    stems: Dict[str, set] = {}
    for name, holds in kernels.items():
        stems.setdefault(name.split(".")[0], set()).add(holds)
    say = {True: "counts", False: "no matrix product", None: "body unreadable, counted"}
    return "; Pallas kernels: " + ", ".join(
        f"{stem} ({'/'.join(say[h] for h in sorted(hs, key=str))})" for stem, hs in sorted(stems.items()))


def run_cell(
    root: str, workload: str, *, seed: int, seconds: float, trace: bool,
    t0: Optional[float] = None, on_chip: bool = True,
) -> Dict[str, Any]:
    """Run one cell and return the contract's result object. ``on_chip=False``
    is for the tests alone: it skips the TPU check and the persistent compile
    cache, and the numbers it returns are never device numbers."""
    t0 = time.time() if t0 is None else t0
    cell = manifest_lib.load_cell(root, workload)
    import jax  # noqa: PLC0415

    devices = check_devices(cell) if on_chip else jax.devices()
    peaks = manifest_lib.load_peaks(root, devices[0].device_kind)
    if on_chip:
        from tpu_dist import compile_cache  # noqa: PLC0415

        _say(f"compile cache: {compile_cache.enable()}")
    from tpu_dist.obs import counters  # noqa: PLC0415

    from benchmarks.harness import reference as reference_lib  # noqa: PLC0415
    from benchmarks.harness.adapter import Adapter  # noqa: PLC0415

    compiles = CompileCounter()
    model = manifest_lib.load_module(root, "models", cell.config["reference"])
    arch = cell.config["arch"]
    flops_per_sample = float(model.train_flops_per_sample(arch))
    ad = Adapter(cell, seed, devices)
    _say(f"cell {cell.name}: global batch {cell.global_batch} on {cell.chips} chip(s), "
         f"{cell.n_train} samples an epoch = {ad.full_epoch_steps} steps, "
         f"{flops_per_sample / 1e9:.3f} GFLOP a sample (analytic, forward+backward)")

    # -- correct (1)/(2), first half: the program's loss at step 0 and its first
    # update, from the seeded initial state. The float32 reference runs after
    # the window (its buffers must not be the allocator's peak, and it is no
    # part of what a user's run sets up); the comparison is made there.
    t = time.perf_counter()
    chk = cell.reference_check
    n_check = chk.get("samples_per_chip")
    inputs, targets = ad.first_batch(None if n_check is None else int(n_check) * cell.chips)
    update = ad.first_update(inputs, targets)
    first = (np.asarray(inputs), np.asarray(targets))
    del inputs, targets
    ad.timing["first_update_s"] = time.perf_counter() - t

    # -- warm-up: every shape the window uses, and a first reading of the rate --
    t = time.perf_counter()
    epoch = 0
    if cell.fused:
        ad.run_epoch(epoch)
        epoch += 1
        rate = None
    else:
        warm = min(int(cell.traffic.get("warmup_steps", WARMUP_STEPS)), ad.full_epoch_steps)
        ad.run_epoch(epoch, steps=warm)
        out = ad.run_epoch(epoch + 1, steps=warm)
        epoch += 2
        rate = out["steps"] / out["wall_s"]
    ad.timing["warmup_s"] = time.perf_counter() - t

    trace_dir = os.path.join(root, "chiprun_out", "trace", cell.name)
    if trace:
        ad.instrument()
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- the window --------------------------------------------------------------
    retraces0 = counters.get("compile.retraces")
    steps0 = counters.get("train.steps")
    before = counters.snapshot()
    compiles.armed = True
    setup_s = time.time() - t0
    epochs: List[Dict[str, Any]] = []
    traced: Optional[Dict[str, Any]] = None
    wall = 0.0
    while True:
        remaining = seconds - wall
        steps = None
        if cell.fused:
            if remaining <= 0:
                break
        else:
            if remaining < 0.05 * seconds and epochs:
                break
            fit = max(1, int(remaining * rate + 0.5))
            if fit < ad.full_epoch_steps:
                steps = fit
        if trace and traced is None:
            if not cell.fused:
                cap = int(cell.traffic.get("trace_steps", TRACE_STEPS))
                steps = min(cap, steps or ad.full_epoch_steps)
                if steps >= ad.full_epoch_steps:
                    steps = None
            opts = jax.profiler.ProfileOptions()
            # the device's side only: the host tracer halves this loop's rate
            # (harness/trace.py); the host's side is the adapter's own spans
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            compiles.armed = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            compiles.armed = True
            traced_t0 = time.perf_counter()
            out = ad.run_epoch(epoch, steps=steps)
            traced_t1 = time.perf_counter()
            compiles.armed = False
            jax.profiler.stop_trace()
            compiles.armed = True
            traced = out
            # host-clock layer metrics are read over the rest of the window:
            # the profiler slows the host, and the loader with it
            before = counters.snapshot()
        else:
            out = ad.run_epoch(epoch, steps=steps)
        epochs.append(out)
        epoch += 1
        wall += out["wall_s"]
        rate = sum(e["steps"] for e in epochs) / wall
    compiles.armed = False
    delta = counters.delta(before, counters.snapshot())
    peak_bytes = ad.peak_bytes()  # before the reference puts its own buffers there
    _say(f"runtime memory after the window, first chip: {ad.memory_stats()[0]}")

    # -- correct (1)/(2), second half: the plain float32 reference -----------------
    t = time.perf_counter()
    ref_loss, ref_grads = reference_lib.reference_loss_and_grads(
        model, arch, update["before"], first[0], first[1],
        int(chk.get("chunk", 0)), devices[0],
    )
    verdict = reference_lib.compare(
        update, ref_loss, ref_grads, cell.config.get("train_config", {}), chk
    )
    _say(f"reference check through {update['through']}: {verdict} "
         f"[{time.perf_counter() - t:.1f} s, after the window]")
    del update, ref_grads, first

    # -- the result ----------------------------------------------------------------
    steps_run = sum(e["steps"] for e in epochs)
    samples = sum(e["samples"] for e in epochs)
    failed = sum(e["steps"] for e in epochs if not math.isfinite(e["loss"]))
    samples_per_s = samples / wall
    chips_peak = cell.chips * float(peaks["bf16_flops_per_s"])
    checks = {
        "reference": verdict["ok"],
        "no_retrace": counters.get("compile.retraces") == retraces0,
        "nothing_compiled_in_window": not compiles.events,
        "samples_are_steps_times_batch": all(
            e["samples_counted_by_program"] == e["samples"] for e in epochs
        ) and (cell.fused or counters.get("train.steps") - steps0 == steps_run),
        "losses_finite": failed == 0,
    }
    _say(f"checks: {checks}" + (f"; compiled in window: {compiles.events}" if compiles.events else ""))
    _say("set-up split: " + ", ".join(f"{k} {v:.2f}" for k, v in ad.timing.items() if k.endswith("_s"))
         + f"; total setup_s {setup_s:.2f}")
    _say(f"window: {len(epochs)} epoch(s), {steps_run} steps, {samples} samples in {wall:.3f} s "
         f"(asked {seconds:g} s); losses {[round(e['loss'], 4) for e in epochs]}")
    values = {
        "samples_per_s": samples_per_s,
        "mfu": 100.0 * samples_per_s * flops_per_sample / chips_peak,
        "peak_hbm_gib": peak_bytes / GIB,
        "setup_s": setup_s,
    }
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak_bytes,
    }
    result: Dict[str, Any] = {
        "correct": all(checks.values()), "attempted": steps_run, "failed": failed,
        "metrics": {}, "device": device,
    }
    if not trace:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        result["compared"] = compared(verdict)
        return result

    from benchmarks.harness import trace as trace_lib  # noqa: PLC0415

    t = time.perf_counter()
    program = ad.program()
    _say(f"program (AOT compile of the window's step, {time.perf_counter() - t:.1f} s): "
         + ", ".join(f"{k} {v / GIB:.3f} GiB" for k, v in program.items() if k.endswith("_bytes"))
         + f", {len(program['matmul_computations'])} computations or kernels hold a matrix product"
         + _kernels_seen(program["mosaic_kernels"]))
    reduced = None
    xplane = trace_lib.find_xplane(trace_dir)
    if xplane is not None:
        t = time.perf_counter()
        capture = trace_lib.load_xplane(xplane)
        spans = [sp for sp in ad.spans_since(traced_t0) if sp[1] < traced_t1]
        dispatches = [a for n, a, _ in spans if n == "bench/dispatch"]
        # a fused epoch is one dispatch, made as the epoch call begins
        trace_lib.align_host(
            capture, (traced_t0, traced_t1), spans,
            dispatches[0] if dispatches else traced_t0,
        )
        reduced = trace_lib.reduce_trace(capture, cell.chips, program["matmul_computations"])
        _say(f"trace {xplane} reduced in {time.perf_counter() - t:.1f} s")
    if reduced is None:
        raise RuntimeError(f"the traced window left no device op under {trace_dir}")
    with open(os.path.join(trace_dir, "program.json"), "w", encoding="utf-8") as f:
        # beside the capture: what its reduction was given besides it
        json.dump({**program, "host": capture.get("host")}, f)
    rest = [e for e in epochs if e is not traced]
    window = {
        "cell": cell, "peaks": peaks, "traced_epoch": traced, "trace": reduced,
        # the window without its traced epoch, for what the host's clock reads
        "epochs": rest, "wall_s": sum(e["wall_s"] for e in rest),
        "steps": sum(e["steps"] for e in rest), "counters": delta,
        "laps_s": ad.laps_s(breaks=(traced_t1,)),
        "flops_per_sample": flops_per_sample, "peak_bytes": peak_bytes,
        "loss_at": ad.loss_at, "say": _say,
    }
    for m in cell.per_layer:
        reader = manifest_lib.load_module(root, "layer_metrics", m["name"])
        value = reader.read(window)
        if value is None:
            _say(f"{m['name']}: not measured")
            continue
        result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
    traced_rate = traced["samples"] / traced["wall_s"]
    if rest:
        rest_rate = sum(e["samples"] for e in rest) / sum(e["wall_s"] for e in rest)
        _say(f"tracing overhead inside this run: {traced_rate:.1f} samples/s under the profiler, "
             f"{rest_rate:.1f} in the rest of the window")
    _say(f"busy per chip: max {reduced['busy_s_max']:.4f} s, min {reduced['busy_s_min']:.4f} s "
         f"of a {reduced['window_s']:.4f} s traced window")
    device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    result["breakdown"] = {
        "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
    }
    result["compared"] = compared(verdict)
    return result
