"""Device cost & efficiency accounting — flops, bytes, peak HBM, MFU.

ONE home for reading XLA's cost/memory analysis out of a train step and
turning it into efficiency numbers, shared by the trainer (first-dispatch
gauges + the per-epoch MFU in every summary/history record) and
``bench.py`` (which previously kept its own private copy of the chip-peak
table and the cost-analysis plumbing).

Everything here is host-side: ``Lowered.cost_analysis()`` runs XLA's
``HloCostAnalysis`` over the traced module without compiling or touching a
device, ``Compiled.cost_analysis()``/``memory_analysis()`` read numbers
XLA already produced while compiling, and :func:`device_memory_stats`
reads the allocator's live counters. Arming any of it adds zero device
work — the TD106/TD107 jaxpr gates pin that.

MFU methodology (``docs/observability.md``): the numerator is the total
FLOPs XLA counts in ONE compiled step (the real fwd+bwd+update HLO, not an
analytic guess — inner ``scan`` bodies are counted once, so callers pass
``loop_trips`` for grad-accumulation/fused-epoch loops); the denominator
is wall seconds per step × the aggregate peak dense-matmul FLOP/s of the
visible chips (:data:`CHIP_PEAK_FLOPS`, public spec-sheet bf16 numbers).
Unknown chip kinds — including CPU emulation — yield ``mfu=None`` rather
than a made-up figure.
"""

from __future__ import annotations

import time
from typing import Optional

from tpu_dist.obs import counters as counters_lib
from tpu_dist.obs import hlo_scopes

# Peak dense matmul FLOP/s per chip (bf16), the MFU denominator. Public
# spec-sheet numbers, keyed by the exact ``device_kind`` JAX reports (plus
# the marketing spellings): a kind that is not here has no row, it does not
# borrow a neighbour's.
CHIP_PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

_GIB = 1024 ** 3
# HBM bytes per jax device (public spec-sheet numbers; a "device" is one
# core on v2/v3 and one megacore chip from v4 on — exactly what
# ``jax.devices()`` enumerates, so the budget divides the way shardings
# do). Keyed like the FLOP table; the pre-flight memory lint
# (``obs/memory.py::preflight_check``) prices configs against this.
CHIP_HBM_BYTES = {
    "TPU v2": 8 * _GIB,
    "TPU v3": 16 * _GIB,
    "TPU v4": 32 * _GIB,
    "TPU v5 lite": 16 * _GIB,
    "TPU v5e": 16 * _GIB,
    "TPU v5p": 95 * _GIB,
    "TPU v5": 95 * _GIB,
    "TPU v6 lite": 32 * _GIB,
    "TPU v6e": 32 * _GIB,
}


def _chip_lookup(table: dict, kind: Optional[str]):
    if kind is None:
        import jax  # noqa: PLC0415

        kind = jax.devices()[0].device_kind
    return table.get(kind)


def require_chip_row(device) -> dict:
    """The table row a MEASUREMENT path prices ``device`` with —
    ``{"kind", "peak_flops", "hbm_bytes"}``. Raises when the device is not
    a TPU or its kind has no row: ``bench.py`` and ``chip_smoke.py`` fail
    there rather than report utilization against a missing or borrowed
    peak (the trainer's own MFU stays ``None`` on unknown kinds)."""
    kind = device.device_kind
    if device.platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices()[0] is platform={device.platform!r} "
            f"kind={kind!r}"
        )
    if kind not in CHIP_PEAK_FLOPS or kind not in CHIP_HBM_BYTES:
        raise RuntimeError(
            f"device_kind {kind!r} has no row in costmodel.CHIP_PEAK_FLOPS/"
            "CHIP_HBM_BYTES — add its published peaks before measuring on it"
        )
    return {
        "kind": kind,
        "peak_flops": CHIP_PEAK_FLOPS[kind],
        "hbm_bytes": CHIP_HBM_BYTES[kind],
    }


def chip_peak_flops(kind: Optional[str] = None) -> Optional[float]:
    """Peak FLOP/s for ``kind`` (default: the first visible device's
    ``device_kind``); None for unknown kinds — CPU emulation above all."""
    return _chip_lookup(CHIP_PEAK_FLOPS, kind)


def chip_hbm_bytes(kind: Optional[str] = None) -> Optional[int]:
    """Per-device HBM budget for ``kind`` (default: the first visible
    device); None for unknown kinds — the memory lint then declines to
    guess rather than refuse a run on a made-up budget."""
    return _chip_lookup(CHIP_HBM_BYTES, kind)


def step_cost(obj, loop_trips: int = 1) -> dict:
    """``{"flops_per_step", "bytes_per_step"}`` of one compiled/lowered
    step (either may be None when XLA reports nothing useful).

    ``loop_trips``: XLA counts a while/scan body ONCE, so steps built
    around an inner loop (grad-accumulation scan, fused-epoch step scan)
    pass the trip count; the body dominates the program, so multiplying
    the whole count errs by at most the loop-external ops (a few %,
    overestimating trips-1 copies of them)."""
    try:
        ca = obj.cost_analysis() or {}
    except Exception:
        return {"flops_per_step": None, "bytes_per_step": None}

    def scaled(key):
        v = ca.get(key)
        return float(v) * loop_trips if v and v > 0 else None

    return {
        "flops_per_step": scaled("flops"),
        "bytes_per_step": scaled("bytes accessed"),
    }


def mfu(
    flops_per_step: Optional[float],
    step_seconds: float,
    n_devices: int,
    peak: Optional[float] = None,
) -> Optional[float]:
    """Model FLOPs utilization: achieved FLOP/s over aggregate chip peak.
    ``peak`` overrides the per-chip table lookup (tests, exotic parts)."""
    if peak is None:
        peak = chip_peak_flops()
    if flops_per_step is None or peak is None or step_seconds <= 0:
        return None
    return round(flops_per_step / step_seconds / (peak * n_devices), 4)


def memory_analysis_bytes(compiled) -> Optional[dict]:
    """Peak-HBM estimate from a Compiled's ``memory_analysis()``: XLA's
    own accounting of argument/output/temp/code bytes for the executable
    (``peak_bytes`` = their sum less buffer aliasing). None when the
    backend does not implement it."""
    try:
        ma = compiled.memory_analysis()
        arg = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
        out = int(getattr(ma, "output_size_in_bytes", 0) or 0)
        tmp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        code = int(getattr(ma, "generated_code_size_in_bytes", 0) or 0)
        alias = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
    except Exception:
        return None
    return {
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": tmp,
        "generated_code_bytes": code,
        "peak_bytes": max(arg + out + tmp + code - alias, 0),
    }


def device_memory_stats() -> Optional[dict]:
    """Live allocator counters across ALL local devices — the TRUE
    peak-HBM gauges on TPU/GPU, updated by the runtime itself. None
    where no backend device keeps stats (CPU).

    The scalar keys (``bytes_in_use`` / ``peak_bytes_in_use`` /
    ``bytes_limit``) report the WORST chip — the max across local
    devices, because HBM is a per-chip constraint and the hottest chip
    is the one that OOMs. (The previous device-0-only read hid exactly
    the failure this exists to surface: an unbalanced sharding whose hot
    chip was any device but 0.) Multi-device processes additionally get
    ``*_min`` floors, ``bytes_in_use_skew`` (max - min, the imbalance
    gauge), and ``mem_devices_reporting``.

    ``peak_bytes`` is the worst chip's ``peak_bytes_in_use`` +
    ``peak_bytes_reserved``: on the TPU runtime the first counts buffers
    only and the executables' temporaries sit in the second (ViT-B/16 at
    batch 128 on a v5e: 2.9 GB beside 9.3 GB, PERF.md), so the sum is the
    chip's peak and the number headroom is reckoned from."""
    try:
        import jax  # noqa: PLC0415

        devices = jax.local_devices()
    except Exception:
        return None
    per: list = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            per.append(stats)
    if not per:
        return None
    out = {}
    for key in (
        "bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit"
    ):
        vals = [
            int(s[key]) for s in per
            if isinstance(s.get(key), (int, float))
        ]
        if not vals:
            continue
        out[key] = max(vals)
        if len(vals) > 1:
            out[f"{key}_min"] = min(vals)
    if "bytes_in_use_min" in out:
        out["bytes_in_use_skew"] = (
            out["bytes_in_use"] - out["bytes_in_use_min"]
        )
    if "peak_bytes_in_use" in out:
        out["peak_bytes"] = max(
            int(s.get("peak_bytes_in_use") or 0)
            + int(s.get("peak_bytes_reserved") or 0)
            for s in per
        )
    if out:
        out["mem_devices_reporting"] = len(per)
    return out or None


# One AOT lower+compile per (step, abstract signature), shared by
# ANALYSIS consumers that re-read the same executable's artifacts — the
# shardlint HLO text + cost analysis + memory waterfall
# (tpu_dist/analysis/shardlint.py) all read ONE compile instead of
# paying three. Long-lived training processes must NOT route their
# one-shot probes through here (see memory_analysis_jitted): values hold
# a strong ref to the jitted wrapper so the id() key cannot be recycled,
# which pins the executable until eviction. Bounded by
# :data:`_COMPILE_CACHE_MAX` (FIFO — the cache exists to dedupe within
# one analysis pass, not to live forever).
_COMPILE_CACHE: dict = {}
_COMPILE_CACHE_MAX = 32


def _aot_key(jitted, args) -> tuple:
    import jax  # noqa: PLC0415

    leaves = jax.tree_util.tree_leaves(args)
    sig = tuple(
        # arrays key on (shape, dtype); non-array leaves (python scalars,
        # static args) key on their VALUE — two lowers of the same jitted
        # fn with different static args must not collide on one executable
        (tuple(x.shape), str(x.dtype))
        if hasattr(x, "shape") and hasattr(x, "dtype")
        else ("val", repr(x)[:128])
        for x in leaves
    )
    return (id(jitted), sig)


def lower_and_compile(jitted, *args):
    """``(Lowered, Compiled)`` of a jitted step at ``args``' abstract
    signature, cached — the lower-and-cache seam every static analysis
    shares. Raises whatever lowering/compiling raises (callers that want
    degradation wrap it; the analyzers want the real error)."""
    key = _aot_key(jitted, args)
    hit = _COMPILE_CACHE.get(key)
    if hit is not None:
        return hit[1], hit[2]
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    if len(_COMPILE_CACHE) >= _COMPILE_CACHE_MAX:
        _COMPILE_CACHE.pop(next(iter(_COMPILE_CACHE)))
    _COMPILE_CACHE[key] = (jitted, lowered, compiled)
    return lowered, compiled


def clear_compile_cache() -> None:
    _COMPILE_CACHE.clear()


def memory_analysis_jitted(jitted, *args) -> Optional[dict]:
    """:func:`memory_analysis_bytes` of a ``jax.jit``-wrapped step: an
    AOT ``lower(...).compile()`` pass purely to read XLA's memory
    waterfall — jax exposes no handle to the executable the first
    dispatch already cached, so this pays ONE extra host-side backend
    compile (the ``jax.monitoring`` listener books it into
    ``compile.seconds``, where the goodput ledger attributes it). The
    trainer therefore captures it once per run and only when telemetry
    consumers exist — and deliberately does NOT go through the
    :func:`lower_and_compile` cache: pinning a second full executable of
    the TRAIN step for the rest of a run would raise steady-state host
    memory on exactly the memory-constrained runs this instruments (the
    cache is for analysis passes that re-read one executable's
    artifacts, e.g. shardlint). None when lowering/compiling is
    unavailable — callers degrade to the ledger without the waterfall,
    never to an error."""
    try:
        compiled = jitted.lower(*args).compile()
    except Exception:
        return None
    return memory_analysis_bytes(compiled)


def analyze_jitted(jitted, *args, loop_trips: int = 1) -> Optional[dict]:
    """Cost-analyze a ``jax.jit``-wrapped step. ``jitted.lower(*args)``
    re-traces abstractly (host-only, no device dispatch) and, where the
    backend can, ``Lowered.cost_analysis()`` runs the HLO cost model over
    the traced module without compiling. The TPU client cannot (measured:
    PR 21, it raises for PJRT plugins): there only the COMPILED executable
    reports, so the step is AOT-compiled once more — a cache load where the
    persistent compile cache is on. Returns :func:`step_cost`'s dict, or
    None when lowering is unavailable — callers degrade to "no MFU",
    never to an error."""
    try:
        lowered = jitted.lower(*args)
    except Exception:
        return None
    cost = step_cost(lowered, loop_trips)
    if cost["flops_per_step"] is None:
        try:
            compiled = lowered.compile()
        except Exception:
            return cost
        cost = step_cost(compiled, loop_trips)
        # the executable is in hand: keep which of its ops lie in which of
        # the program's named scopes, for whoever reads a device trace
        t0 = time.perf_counter()
        try:
            hlo_scopes.record(compiled.as_text(), hlo_scopes.opened())
        except Exception:
            hlo_scopes.record("")  # no table rather than a stale or half one
        counters_lib.inc("compile.scope_table_s", round(time.perf_counter() - t0, 3))
    return cost


class CompileWatcher:
    """Turn a jitted step's executable-cache growth into compile telemetry.

    jax keeps one compiled executable per (shape, dtype, static-arg)
    signature; the cache growing past the expected warmup mid-run means
    the step RETRACED — usually shape/dtype drift in the input pipeline,
    and on a pod each retrace is a full XLA compile stall on every host.
    Callers invoke :meth:`observe` once per step (one C++ attribute
    read — no device work, no sync): every growth increments
    ``compile.events``; growth after the first dispatch (or after
    :meth:`baseline`) additionally increments ``compile.retraces``,
    prints the rank-0 warning, and returns True. The warning and the
    counters live HERE — the trainer, the serving engine, and any future
    caller get the same surfacing for free; ``obs summarize`` reports
    the per-epoch retrace delta.

    Multi-signature callers (the serving engine compiles one executable
    per batch bucket at warmup) call :meth:`baseline` after their warmup
    pass: the compiles so far are absorbed as expected (counted into
    ``compile.events``, never as retraces) and EVERY later growth is a
    retrace.

    Degrades to a permanent no-op when the callable has no
    ``_cache_size`` (a non-jit wrapper, or a jax that dropped the
    private API) — observation must never break the step loop."""

    def __init__(self, jitted, name: str = "train step", warn: bool = True):
        self._size_fn = getattr(jitted, "_cache_size", None)
        self._seen = 0
        self._baselined = False
        self.name = name
        self.warn = warn

    def _size(self) -> Optional[int]:
        if self._size_fn is None:
            return None
        try:
            return int(self._size_fn())
        except Exception:
            self._size_fn = None
            return None

    def baseline(self) -> int:
        """Absorb every compile so far as expected warmup: counts them
        into ``compile.events`` but never as retraces, and marks the
        watcher so ANY later growth is one. Returns the absorbed count."""
        size = self._size()
        if size is None:
            return 0
        grew = max(size - self._seen, 0)
        if grew:
            counters_lib.inc("compile.events", grew)
        self._seen = max(size, self._seen)
        self._baselined = True
        return grew

    def observe(self, context: str = "") -> bool:
        """Record any new compiles; True when one was a mid-run retrace.
        On a retrace the watcher itself prints the rank-0 warning
        (``warn=False`` to suppress); ``context`` names the position
        (``"epoch 3 step 12"``) in it."""
        size = self._size()
        if size is None or size <= self._seen:
            return False
        grew = size - self._seen
        first = self._seen == 0 and not self._baselined
        self._seen = size
        counters_lib.inc("compile.events", grew)
        retraces = grew - 1 if first else grew
        if retraces > 0:
            counters_lib.inc("compile.retraces", retraces)
            if self.warn:
                from tpu_dist.metrics.logging import rank0_print  # noqa: PLC0415

                rank0_print(
                    f"WARNING: {self.name} RECOMPILED"
                    + (f" at {context}" if context else "")
                    + " — input shape/dtype drift? (compile.retraces="
                    f"{counters_lib.get('compile.retraces'):g})"
                )
            return True
        return False


_LISTENER_INSTALLED = False


def install_compile_listener() -> None:
    """Accumulate XLA's own backend-compile wall time into the
    ``compile.seconds`` counter via ``jax.monitoring`` (fires for every
    compile in the process — train step, eval step, fused paths alike).
    Idempotent; jax offers no unregistration, so ONE process-lifetime
    listener feeds the process-global counter registry."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    from jax import monitoring  # noqa: PLC0415

    def _on_event(event: str, duration: float, **kw) -> None:
        # backend_compile ONLY: one jit compile also fires nested
        # jaxpr_trace / jaxpr_to_mlir_module duration events whose
        # wall times overlap it — summing every "compile"-ish event
        # would over-count real elapsed time severalfold
        if "backend_compile" in event:
            counters_lib.inc("compile.seconds", round(float(duration), 3))

    monitoring.register_event_duration_secs_listener(_on_event)
    _LISTENER_INSTALLED = True


def _sig(v: float, digits: int = 4) -> float:
    """Round to significant digits — calibration rates span 1e3..1e15."""
    return float(f"{v:.{digits}g}")


def calibration(
    cost: Optional[dict],
    analysis: Optional[dict],
    *,
    steps: Optional[int] = None,
    n_devices: int = 1,
    peak: Optional[float] = None,
) -> dict:
    """Calibrate the static cost model against a measured capture: divide
    the xprof attribution's measured category seconds into the predicted
    per-step FLOPs/bytes (``step_cost``) and return achieved-rate /
    drift gauges, keyed by their registry names:

    * ``cost.calibration_flops_per_s`` — AGGREGATE achieved FLOP/s over
      the capture's COMPUTE seconds only (matmul/conv + fusion), i.e.
      what the hardware sustains when it is actually computing
      (MFU is whole-step wall over chip peak).
    * ``cost.calibration_compute_frac`` — that rate over the AGGREGATE
      chip peak (``peak × n_devices``, :func:`mfu`'s denominator —
      ``flops_per_step`` is treated as the step's total across devices,
      the SAME convention ``mfu`` applies to the same ``step_cost``
      dict, so the two published efficiency numbers always agree);
      omitted on unknown chips (CPU emulation).
    * ``cost.calibration_bytes_per_s`` — aggregate achieved bytes/s:
      the cost model's per-step byte count over measured busy seconds.
    * ``cost.calibration_collective_frac`` / ``_overlap_frac`` — the
      capture's collective share of device busy time and comm/compute
      overlap fraction, the two schedule-quality drift signals.
    * ``cost.calibration_steps`` — steps the capture covered (the
      normalization the rates used).

    ``analysis`` is the compact xprof record; ``steps`` the step count
    the capture covered (rate gauges need it; the fraction gauges work
    without). Returns {} when nothing is computable — callers publish
    whatever comes back and never fail a capture on a thin one."""
    out: dict = {}
    if not analysis:
        return out
    cf = analysis.get("collective_frac")
    if isinstance(cf, (int, float)):
        out["cost.calibration_collective_frac"] = cf
    ov = analysis.get("overlap_frac")
    if ov is None and isinstance(analysis.get("overlap"), dict):
        ov = analysis["overlap"].get("overlap_frac")
    if isinstance(ov, (int, float)):
        out["cost.calibration_overlap_frac"] = ov
    busy = analysis.get("device_busy_s")
    cats = analysis.get("categories") or {}
    if not steps or not isinstance(busy, (int, float)) or busy <= 0:
        return out
    out["cost.calibration_steps"] = int(steps)
    n_devices = max(int(n_devices), 1)
    cost = cost or {}
    # measured seconds are SUMMED across the capture's devices, so the
    # concurrent-wall compute time per step is compute_s/steps/n_devices;
    # flops_per_step is the step's aggregate count (the mfu convention),
    # so the ratio is the aggregate achieved rate
    compute_s = (
        float(cats.get("matmul_conv", 0.0)) + float(cats.get("fusion_other", 0.0))
    )
    flops = cost.get("flops_per_step")
    if isinstance(flops, (int, float)) and flops > 0 and compute_s > 0:
        achieved = flops / (compute_s / steps / n_devices)
        out["cost.calibration_flops_per_s"] = _sig(achieved)
        if peak is None:
            peak = chip_peak_flops()
        if peak:
            out["cost.calibration_compute_frac"] = round(
                achieved / (peak * n_devices), 4
            )
    byts = cost.get("bytes_per_step")
    if isinstance(byts, (int, float)) and byts > 0:
        out["cost.calibration_bytes_per_s"] = _sig(
            byts / (busy / steps / n_devices)
        )
    return out


def publish_calibration(gauges: dict) -> None:
    """Stamp :func:`calibration`'s gauges into the telemetry registry —
    every later history record and OpenMetrics exposition carries them
    (``counters.snapshot`` feeds both)."""
    for name, v in gauges.items():
        counters_lib.set_gauge(name, v)


def predicted_step_time(
    cost: Optional[dict],
    *,
    wire_bytes: Optional[int] = None,
    n_devices: int = 1,
    gauges: Optional[dict] = None,
    peak: Optional[float] = None,
) -> dict:
    """Static step-time prediction, corrected by the latest measured
    ``cost.calibration_*`` gauges (the shard report stamps it per config
    family).

    Model (documented, deliberately simple): compute time is the step's
    FLOPs over the ACHIEVED FLOP/s from the last calibrated capture
    (falling back to the spec-sheet chip peak when no capture exists —
    ``source`` says which); memory time is XLA's bytes-accessed over the
    achieved bytes/s; communication time is the HLO wire bytes over the
    same achieved bytes/s (a proxy until an ICI-rate gauge exists —
    recorded as such). Compute and memory overlap perfectly inside a
    fused step (``max``); communication hides behind compute by the
    measured ``overlap_frac`` (0 when never measured). Returns ``{}``
    when there is nothing to price (no flops and no bytes)."""
    gauges = gauges if gauges is not None else counters_lib.snapshot()
    cost = cost or {}
    flops = cost.get("flops_per_step")
    byts = cost.get("bytes_per_step")
    flops_rate = gauges.get("cost.calibration_flops_per_s")
    bytes_rate = gauges.get("cost.calibration_bytes_per_s")
    overlap = gauges.get("cost.calibration_overlap_frac") or 0.0
    source = "calibrated"
    if not isinstance(flops_rate, (int, float)) or flops_rate <= 0:
        if peak is None:
            peak = chip_peak_flops()
        flops_rate = peak * n_devices if peak else None
        source = "spec_peak"
    out: dict = {}
    t_compute = (
        flops / flops_rate
        if isinstance(flops, (int, float)) and flops > 0 and flops_rate
        else None
    )
    t_mem = (
        byts / bytes_rate
        if isinstance(byts, (int, float)) and byts > 0
        and isinstance(bytes_rate, (int, float)) and bytes_rate > 0
        else None
    )
    t_comm = (
        wire_bytes / bytes_rate
        if isinstance(wire_bytes, (int, float)) and wire_bytes > 0
        and isinstance(bytes_rate, (int, float)) and bytes_rate > 0
        else None
    )
    if t_compute is None and t_mem is None:
        return out
    busy = max(t for t in (t_compute, t_mem) if t is not None)
    exposed_comm = (t_comm or 0.0) * (1.0 - min(max(overlap, 0.0), 1.0))
    out = {
        "predicted_step_s": _sig(busy + exposed_comm),
        "compute_s": _sig(t_compute) if t_compute is not None else None,
        "memory_s": _sig(t_mem) if t_mem is not None else None,
        "comm_s": _sig(t_comm) if t_comm is not None else None,
        "overlap_frac_applied": round(float(overlap), 4),
        "rate_source": source,
    }
    return out


def publish(cost: Optional[dict]) -> None:
    """Stamp a step-cost dict into the telemetry gauges
    (``device.flops_per_step`` / ``device.bytes_per_step``) so every
    history record carries the numbers next to the throughput they
    explain."""
    if not cost:
        return
    for key, gauge in (
        ("flops_per_step", "device.flops_per_step"),
        ("bytes_per_step", "device.bytes_per_step"),
    ):
        v = cost.get(key)
        if v is not None:
            counters_lib.set_gauge(gauge, v)
