"""Benchmark harness: training throughput on TPU, one JSON line on stdout.

Default (driver contract): ResNet-18 / CIFAR-100 — the reference's headline
benchmark — printing
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}``.

Baseline (BASELINE.md): the reference's best row, DDP + apex on
4×RTX 2080 Ti: 14.5 s/epoch over CIFAR-100's 50,000 images ≈ 3,448 img/s
aggregate. ``vs_baseline`` = our aggregate images/sec ÷ that (>1 beats the
whole 4-GPU rig).

More configs (BASELINE.json's matrix) via ``--config``:

    python bench.py --config resnet18_cifar100      # default, bf16
    python bench.py --config resnet18_cifar100_fp32
    python bench.py --config resnet18_cifar100_ga4  # grad accumulation 4
    python bench.py --config resnet50_imagenet      # 224x224, bf16
    python bench.py --config vit_b16_imagenet       # transformer grads

Measures the steady-state compiled train step (warmup excluded), reference
hyperparameters (SGD+momentum+wd, SyncBN on for the conv nets).

A measurement needs the chip: without a TPU ``main`` exits non-zero and
prints no metric line, and a failure inside a measurement fails the command
(only the XLA attention path running out of HBM at long S is an expected
outcome). The ``run*`` functions stay importable for the CPU tests.
"""

from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass

import numpy as np

BASELINE_IMG_PER_SEC = 50_000 / 14.5  # DDP+apex, 4x2080Ti (README.md:77)
CIFAR_TRAIN = 50_000


def _capture_fingerprint() -> dict:
    """One fingerprint per bench PROCESS (hostname + random id), stamped
    with a monotonic capture time into every emitted record. Two records
    carrying the SAME fingerprint are the same physical capture: a
    later artifact re-emitting it byte-identically is a stale copy, not
    a fresh measurement, which ``obs compare --bench`` / ``obs summarize
    --bench`` flag as STALE instead of reporting as fresh."""
    import socket  # noqa: PLC0415
    import uuid  # noqa: PLC0415

    return {"host": socket.gethostname(), "bench_run_id": uuid.uuid4().hex[:12]}


_CAPTURE = _capture_fingerprint()

#: Every record this process emitted (``_stamped`` appends) — the
#: ``--archive`` self-ingest reads this at exit so the longitudinal
#: archive (``tpu_dist/obs/archive.py``) stays current without a
#: separate ingest step.
_EMITTED: list = []


def _stamped(rec: dict) -> dict:
    rec["capture"] = {**_CAPTURE, "mono_s": round(time.monotonic(), 3)}
    _EMITTED.append(rec)
    return rec


def _self_ingest(path: str, records=None) -> None:
    """Fold this invocation's records into the longitudinal archive.
    NEVER dies: a broken archive must not fail the bench that measured
    fine — the failure is counted to stderr instead (the archive's own
    loader counts torn/foreign lines the same way)."""
    import sys  # noqa: PLC0415

    recs = _EMITTED if records is None else records
    if not recs:
        return
    try:
        from tpu_dist.obs import archive as archive_lib  # noqa: PLC0415

        rep = archive_lib.ingest_records(recs, path, source_path="bench.py")
        print(
            f"bench: archived {rep['appended']} record(s) to {path}"
            + (f" ({rep['deduped']} already present)"
               if rep["deduped"] else ""),
            file=sys.stderr, flush=True,
        )
    except Exception as e:  # the never-dies contract: count, don't raise
        print(
            f"bench: archive self-ingest FAILED ({len(recs)} record(s) "
            f"NOT archived): {type(e).__name__}: {e}",
            file=sys.stderr, flush=True,
        )


def _costmodel():
    """The shared cost/MFU layer (``tpu_dist.obs.costmodel``) — ONE home
    for the chip-peak table, the ``cost_analysis()`` normalization, and
    ``memory_analysis()`` reading that this file used to keep private
    copies of. Imported lazily like every tpu_dist import here (argparse
    runs before any backend touch)."""
    from tpu_dist.obs import costmodel

    return costmodel


def _step_cost(compiled, loop_trips: int = 1) -> dict:
    """flops/bytes of one compiled step (see ``costmodel.step_cost`` for
    the scan-body ``loop_trips`` contract); all-None on failure."""
    return _costmodel().step_cost(compiled, loop_trips)


def _mfu(flops_per_step: float | None, step_seconds: float, n_devices: int) -> float | None:
    """Model FLOPs utilization: achieved FLOP/s over aggregate chip peak
    (None on unknown chips — CPU emulation above all)."""
    return _costmodel().mfu(flops_per_step, step_seconds, n_devices)


def _hbm_fields(compiled) -> dict:
    """XLA's own executable memory accounting, when the backend reports it:
    ``{"peak_hbm_bytes": ...}`` or empty."""
    ma = _costmodel().memory_analysis_bytes(compiled)
    return {"peak_hbm_bytes": ma["peak_bytes"]} if ma else {}


def _wire_audit(fn, *args, trips: int = 1) -> dict | None:
    """Static wire-byte accounting of a compiled step/epoch's gradient
    collectives (the jaxpr-level TD104 model from ``tpu_dist.analysis``),
    normalized to ONE step via ``trips``. An abstract trace — valid on CPU
    emulation, where the --grad_compression sweep's throughput numbers are
    not. Returns None (with a stderr note — this is the sweep's headline
    metric, a silent drop would read as 'audit unavailable') on failure."""
    import sys

    try:
        from tpu_dist.analysis.jaxpr_audit import trace_counts

        w = trace_counts(fn, *args)["wire"]
        return {
            k: w[k] // trips
            for k in ("payload_bytes", "quantized_payload_bytes", "sideband_bytes")
        }
    except Exception as e:
        print(f"bench: wire-byte audit failed ({type(e).__name__}: "
              f"{(str(e).splitlines() or [''])[0][:160]})",
              file=sys.stderr, flush=True)
        return None


def _hlo_wire_audit(
    compiled, loop_trips: int = 1, per_step_div: int = 1
) -> int | None:
    """HLO-derived wire bytes of ONE step, from the optimized module the
    compiler actually emitted (the shardlint parser over
    ``Compiled.as_text()`` — tpu_dist/analysis/shardlint.py). Stamped
    beside the jaxpr ring model's ``wire_bytes_per_step`` so the two
    accountings ride every bench record together, and gated by ``obs
    compare --bench`` (higher = a compiled-comm regression: GSPMD grew a
    reshard the jaxpr can't see). ``loop_trips`` prices ``while``-body
    collectives at their trip count; ``per_step_div`` normalizes a
    whole-epoch scan program back to one step. The two are SEPARATE so a
    grad-accumulation step (trips=K, div=1) shows a collective that
    drifted INTO the accumulation loop as a Kx wire regression instead
    of hiding it. None (with a stderr note) on failure. A static count,
    so it gates in CI, where there is no chip."""
    import sys

    try:
        from tpu_dist.analysis.shardlint import parse_hlo_collectives

        ops = parse_hlo_collectives(compiled.as_text(), loop_trips=loop_trips)
        return sum(op.wire_bytes for op in ops) // per_step_div
    except Exception as e:
        print(f"bench: HLO wire-byte audit failed ({type(e).__name__}: "
              f"{(str(e).splitlines() or [''])[0][:160]})",
              file=sys.stderr, flush=True)
        return None


@dataclass(frozen=True)
class BenchConfig:
    name: str
    model: str
    image_size: int
    num_classes: int
    global_batch: int
    bf16: bool = True
    grad_accum: int = 1
    sync_bn: bool = True
    fused_epoch: bool = False  # device-resident data, one jit per epoch
    flash: bool = False        # Pallas tiled attention (transformer models)
    s2d: bool = False          # space-to-depth stem (ImageNet ResNet only)
    epoch_images: int = CIFAR_TRAIN  # for sec/epoch derivation


CONFIGS = {
    c.name: c
    for c in [
        BenchConfig("resnet18_cifar100", "resnet18", 32, 100, 256),
        BenchConfig("resnet18_cifar100_fp32", "resnet18", 32, 100, 256, bf16=False),
        BenchConfig("resnet18_cifar100_ga4", "resnet18", 32, 100, 256, grad_accum=4),
        BenchConfig("resnet18_cifar100_fused", "resnet18", 32, 100, 256, fused_epoch=True),
        BenchConfig(
            "resnet50_imagenet", "resnet50_imagenet", 224, 1000, 128,
            epoch_images=1_281_167,
        ),
        # same model, space-to-depth stem (MXU-utilization rewrite of the
        # 7x7/2 C_in=3 conv; numerics-identical, nn/resnet.py::_stem_s2d)
        BenchConfig(
            "resnet50_imagenet_s2d", "resnet50_imagenet", 224, 1000, 128,
            s2d=True, epoch_images=1_281_167,
        ),
        BenchConfig(
            "vit_b16_imagenet", "vit_b16", 224, 1000, 64,
            sync_bn=False, epoch_images=1_281_167,
        ),
        BenchConfig(
            "vit_b16_imagenet_flash", "vit_b16", 224, 1000, 64,
            sync_bn=False, flash=True, epoch_images=1_281_167,
        ),
        # long-context showcase: 1024px -> S = 64^2+1 = 4097 tokens; the
        # full train step (not just the attention micro-bench) at a length
        # where the XLA path's score tensor is the memory bottleneck
        BenchConfig(
            "vit_b16_1024px_flash", "vit_b16", 1024, 1000, 8,
            sync_bn=False, flash=True, epoch_images=1_281_167,
        ),
        BenchConfig(
            "vit_b16_1024px_xla", "vit_b16", 1024, 1000, 8,
            sync_bn=False, epoch_images=1_281_167,
        ),
    ]
}


def run(cfg: BenchConfig, steps: int, warmup: int, n_devices: int | None = None,
        profile_dir: str | None = None, grad_compression: str = "none") -> dict:
    # goodput accounting opens with the bench itself: everything from here
    # to the record — model init, compile, warmup — is overhead the
    # measured loop amortizes, and goodput_frac = measured-loop seconds /
    # total wall is the CPU-valid time-accounting signal the trainer's
    # run ledger reports at scale (obs/goodput.py)
    t_bench0 = time.perf_counter()
    import jax
    import jax.numpy as jnp

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.nn import resnet18, resnet34, resnet50
    from tpu_dist.nn.resnet import resnet50_imagenet
    from tpu_dist.nn.vit import vit_b16
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.state import TrainState
    from tpu_dist.train.step import make_train_step

    models = {
        "resnet18": resnet18, "resnet34": resnet34, "resnet50": resnet50,
        "resnet50_imagenet": lambda num_classes: resnet50_imagenet(
            num_classes, s2d_stem=cfg.s2d
        ),
        "vit_b16": lambda num_classes: vit_b16(num_classes, cfg.image_size),
    }
    from tpu_dist.nn.attention import set_default_attention_impl

    # process-global: reset per run so --all mixes flash and by-shape configs safely
    set_default_attention_impl("flash" if cfg.flash else "auto")
    if n_devices is None:
        mesh = mesh_lib.data_parallel_mesh()
    else:
        mesh = mesh_lib.device_mesh(
            [n_devices], [mesh_lib.DATA_AXIS], jax.devices()[:n_devices]
        )
    n_dev = int(mesh.devices.size)
    batch = cfg.global_batch
    if batch % (n_dev * cfg.grad_accum):
        batch = n_dev * cfg.grad_accum * max(1, batch // (n_dev * cfg.grad_accum))

    model = models[cfg.model](num_classes=cfg.num_classes)
    optimizer = SGD(momentum=0.9, weight_decay=1e-4)
    params, bn_state = model.init(jax.random.PRNGKey(0))
    state = jax.device_put(
        TrainState.create(params, bn_state, optimizer), mesh_lib.replicated(mesh)
    )
    if grad_compression == "int8_ef":
        from tpu_dist.train.step import init_ef_state

        state = state._replace(ef=init_ef_state(params, mesh))
    if cfg.fused_epoch:
        return _run_fused(
            cfg, mesh, model, optimizer, state, n_dev, batch,
            grad_compression=grad_compression, t_bench0=t_bench0,
        )
    step = make_train_step(
        model.apply,
        optimizer,
        mesh,
        grad_accum_steps=cfg.grad_accum,
        sync_bn=cfg.sync_bn,
        compute_dtype=jnp.bfloat16 if cfg.bf16 else jnp.float32,
        grad_compression=grad_compression,
    )

    rng = np.random.default_rng(0)
    images = mesh_lib.shard_batch(
        mesh, rng.normal(size=(batch, cfg.image_size, cfg.image_size, 3)).astype(np.float32)
    )
    labels = mesh_lib.shard_batch(
        mesh, rng.integers(0, cfg.num_classes, batch).astype(np.int32)
    )

    wire = _wire_audit(step, state, images, labels, 0.1)

    # AOT-compile once: the same executable serves cost analysis (MFU
    # numerator), memory accounting, AND the measured loop — no double
    # compile. A compile failure fails the measurement.
    call = step.lower(state, images, labels, 0.1).compile()
    cost = _step_cost(call, loop_trips=cfg.grad_accum)
    hbm = _hbm_fields(call)
    hlo_wire = _hlo_wire_audit(call, loop_trips=cfg.grad_accum)
    flops_per_step = cost["flops_per_step"]

    for _ in range(warmup):
        state, metrics = call(state, images, labels, 0.1)
    jax.block_until_ready(state.params)

    import contextlib

    from tpu_dist.obs.profile import StepTimer, trace

    prof = trace(profile_dir) if profile_dir else contextlib.nullcontext()
    with prof:
        # per-step laps WITHOUT a per-step sync (StepTimer discipline): the
        # device queue's backpressure paces the enqueues at the real step
        # rate in steady state, so the percentiles see stalls/jitter while
        # the hot loop stays sync-free; only the final block is exact.
        timer = StepTimer(warmup_steps=1)
        timer.tick()  # baseline mark (the warmup loop above already ran)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = call(state, images, labels, 0.1)
            timer.tick()
        jax.block_until_ready(state.params)
        dt = time.perf_counter() - t0

    img_per_sec = batch * steps / dt
    tag = "" if grad_compression == "none" else f"_{grad_compression}"
    pct = timer.percentiles() or {}
    out = {
        "metric": f"{cfg.name}{tag}_train_throughput",
        "value": round(img_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
        "sec_per_epoch": round(cfg.epoch_images / img_per_sec, 2),
        "n_devices": n_dev,
        "global_batch": batch,
        "img_per_sec_per_chip": round(img_per_sec / n_dev, 1),
        "step_ms": round(1000 * dt / steps, 2),
        # tail latency in the same schema the trainer's epoch summary and
        # `tpu_dist.obs summarize` report (p50/p95/p99), bench's ms units
        **{
            f"step_ms_{q}": round(1000 * v, 2) for q, v in sorted(pct.items())
        },
        "mfu": _mfu(flops_per_step, dt / steps, n_dev),
        # measured-loop seconds over total bench wall (compile + warmup
        # included): the bench-local goodput fraction
        "goodput_frac": round(dt / (time.perf_counter() - t_bench0), 4),
        # XLA's per-step cost accounting next to the throughput it explains
        # (same numbers the trainer publishes as device.* gauges)
        "flops_per_step": cost["flops_per_step"],
        "bytes_per_step": cost["bytes_per_step"],
        **hbm,
    }
    if grad_compression != "none":
        out["grad_compression"] = grad_compression
    if wire is not None:
        out["wire_bytes_per_step"] = wire
    if hlo_wire is not None:
        out["hlo_wire_bytes_per_step"] = hlo_wire
    if profile_dir:
        # read the capture back (obs/xprof): the attribution lands next to
        # the throughput it explains — a bench line with 40% collective
        # share and 10% overlap names its own bottleneck
        from tpu_dist.obs.profile import analyze_capture_quietly

        analysis, a_err = analyze_capture_quietly(profile_dir)
        if analysis is not None:
            out["profile_analysis"] = {
                k: analysis.get(k)
                for k in ("device_busy_s", "collective_frac",
                          "overlap_frac", "infeed_stall_s")
            }
        elif a_err:
            out["profile_analysis_error"] = a_err
    return _stamped(out)


def _run_fused(cfg: BenchConfig, mesh, model, optimizer, state, n_dev: int,
               batch: int, grad_compression: str = "none",
               t_bench0: float | None = None) -> dict:
    """Bench the device-resident fused-epoch path on the real 50k dataset:
    measures true seconds/epoch including shuffle + augmentation (all
    on-device), one jit call per epoch."""
    import time as _t

    import jax
    import jax.numpy as jnp

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.data import synthetic_cifar
    from tpu_dist.train.epoch import make_fused_epoch, put_dataset_on_device

    imgs, lbls = synthetic_cifar(CIFAR_TRAIN, cfg.num_classes, cfg.image_size)
    dx, dy = put_dataset_on_device(mesh, imgs, lbls)
    runner = make_fused_epoch(
        model.apply, optimizer, mesh,
        batch_per_device=batch // n_dev,
        sync_bn=cfg.sync_bn,
        compute_dtype=jnp.bfloat16 if cfg.bf16 else jnp.float32,
        grad_compression=grad_compression,
    )
    from tpu_dist.train.epoch import fused_steps_per_epoch

    steps_per_epoch = fused_steps_per_epoch(int(dx.shape[0]), batch)
    # whole-epoch program: the scan multiplies per-trip collectives, so
    # normalize the audit back to one step
    wire = _wire_audit(runner, state, dx, dy, 0.1, 0, trips=steps_per_epoch)
    # AOT-compile once (cost analysis + the measured loop share it)
    call = runner.lower(state, dx, dy, 0.1, 0).compile()
    cost = _step_cost(call, loop_trips=steps_per_epoch)
    hbm = _hbm_fields(call)
    hlo_wire = _hlo_wire_audit(
        call, loop_trips=steps_per_epoch, per_step_div=steps_per_epoch,
    )
    flops_per_epoch = cost["flops_per_step"]  # trips-scaled: whole epoch

    # warmup epoch
    state, m = call(state, dx, dy, 0.1, 0)
    jax.block_until_ready(state.params)

    n_epochs = 3
    t0 = _t.perf_counter()
    for e in range(1, n_epochs + 1):
        state, m = call(state, dx, dy, 0.1, e)
    jax.block_until_ready(state.params)
    dt = (_t.perf_counter() - t0) / n_epochs

    n_images = int(dx.shape[0])
    img_per_sec = n_images / dt
    tag = "" if grad_compression == "none" else f"_{grad_compression}"
    out = {
        "metric": f"{cfg.name}{tag}_train_throughput",
        "value": round(img_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
        "sec_per_epoch": round(dt, 2),
        "n_devices": n_dev,
        "global_batch": batch,
        "img_per_sec_per_chip": round(img_per_sec / n_dev, 1),
        "mfu": _mfu(flops_per_epoch, dt, n_dev),
        "goodput_frac": (
            round(
                (dt * n_epochs)
                / (_t.perf_counter() - t_bench0), 4,
            ) if t_bench0 is not None else None
        ),
        # per-STEP accounting (divide the trips-scaled epoch totals back)
        "flops_per_step": (
            round(flops_per_epoch / steps_per_epoch)
            if flops_per_epoch else None
        ),
        "bytes_per_step": (
            round(cost["bytes_per_step"] / steps_per_epoch)
            if cost["bytes_per_step"] else None
        ),
        **hbm,
    }
    if grad_compression != "none":
        out["grad_compression"] = grad_compression
    if wire is not None:
        out["wire_bytes_per_step"] = wire
    if hlo_wire is not None:
        out["hlo_wire_bytes_per_step"] = hlo_wire
    return _stamped(out)


def run_attn(seq_len: int, steps: int, warmup: int, *, batch: int = 0,
             causal: bool = False) -> dict:
    """Long-sequence attention micro-bench: Pallas flash kernel vs the XLA
    [S,S]-materializing path, fwd+bwd, one JSON line.

    The reference has no attention at all (SURVEY §2.3); this is the
    long-context showcase for ``ops/flash_attention.py`` — at lengths where
    the XLA path's [B·H, S, S] f32 score tensor stops fitting in HBM
    (S=16k at these shapes wants ~17 GB for the scores alone on a 16 GB
    chip), flash keeps O(block²) per-core working sets. ``vs_baseline``
    here = flash speedup over the XLA path (>1 means the kernel wins;
    null when XLA ran out of HBM — the one failure this function records
    rather than raises; a flash failure fails the command).
    """
    import jax
    import jax.numpy as jnp

    from tpu_dist.nn.attention import full_attention

    heads, d_head = 8, 128  # model dim 1024, MXU-native 128-lane head dim
    if batch <= 0:
        batch = max(1, 32_768 // seq_len)  # ~32k tokens per step
    shape = (batch, seq_len, heads, d_head)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    def bench_impl(impl: str):
        def loss(q, k, v):
            if impl == "flash_xla_bwd":  # A/B: Pallas fwd, lax.scan bwd
                from tpu_dist.ops.flash_attention import flash_attention

                out = flash_attention(q, k, v, causal=causal, bwd="xla")
            else:
                out = full_attention(q, k, v, causal=causal, impl=impl)
            return out.astype(jnp.float32).sum()

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        call = step.lower(q, k, v).compile()
        for _ in range(warmup):
            jax.block_until_ready(call(q, k, v))
        t0 = time.perf_counter()
        for _ in range(steps):
            out = call(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps

    flash_s = bench_impl("flash")
    xla_s, xla_err = None, None
    try:
        xla_s = bench_impl("xla")
    except jax.errors.JaxRuntimeError as e:
        # the [B·H, S, S] score tensor outgrowing HBM at long S is the point
        # of the comparison; anything else is a failure
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        xla_err = (str(e).splitlines() or [""])[0][:160]
    # the Pallas backward vs the XLA-scan backward, same forward
    fxb_s = bench_impl("flash_xla_bwd")

    # analytic fwd+bwd FLOPs (QK^T + PV fwd = 4·S²·D/head; FA2 bwd ≈ 2.5×):
    # XLA cost analysis can't see inside pallas_call, so both impls use the
    # same formula — MFU comparable across the two columns
    flops = 14.0 * batch * heads * seq_len * seq_len * d_head
    if causal:
        flops /= 2
    tok_per_sec = round(batch * seq_len / flash_s, 1)
    return _stamped({
        "metric": f"attn_s{seq_len}{'_causal' if causal else ''}_flash_fwd_bwd",
        "value": tok_per_sec,
        "unit": "tokens/sec",
        "vs_baseline": round(xla_s / flash_s, 3) if xla_s else None,
        "seq_len": seq_len,
        "batch": batch,
        "heads": heads,
        "head_dim": d_head,
        "flash_ms": round(1000 * flash_s, 2),
        "xla_ms": round(1000 * xla_s, 2) if xla_s else None,
        "flash_xla_bwd_ms": round(1000 * fxb_s, 2),
        "xla_err": xla_err,
        "mfu": _mfu(flops, flash_s, 1),
        "xla_mfu": _mfu(flops, xla_s, 1) if xla_s else None,
    })


def run_pp(cfg: BenchConfig, steps: int, warmup: int, pp: int,
           interleave: int, microbatches: int, dims: str = "b16") -> dict:
    """Pipeline-parallel bench: ViT-B/16 split into ``pp`` stages over a
    (data × pipe) mesh, GPipe (``interleave=1``) or interleaved virtual
    stages, with the schedule's bubble fraction in the output line.

    Needs ``pp`` to divide the visible device count — on the single-chip
    TPU run it with CPU host-platform emulation
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``); on a real
    multi-chip slice it measures the ICI pipeline directly.
    """
    t_bench0 = time.perf_counter()
    import jax
    import jax.numpy as jnp

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.nn.vit_pp import ViTPipelineDef
    from tpu_dist.parallel.pipeline import bubble_fraction
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.state import TrainState
    from tpu_dist.train.step import make_train_step

    if cfg.model != "vit_b16":
        raise SystemExit("--pp bench supports --config vit_b16_imagenet only")
    n = len(jax.devices())
    if n % pp:
        raise SystemExit(f"{n} devices not divisible by pp={pp}")
    # tiny dims: smoke/validate the schedule on CPU emulation; b16: the
    # real measurement shape
    depth, dim, heads, patch, img = (
        (12, 768, 12, 16, cfg.image_size) if dims == "b16"
        else (8, 64, 4, 4, 32)
    )
    if depth % (pp * interleave):
        raise SystemExit(
            f"depth {depth} must divide into pp*interleave={pp * interleave} "
            "equal chunks (try pp in {2,3,4,6,12}, interleave such that "
            f"pp*interleave divides {depth})"
        )
    mesh = mesh_lib.device_mesh(
        [n // pp, pp], [mesh_lib.DATA_AXIS, mesh_lib.PIPE_AXIS]
    )
    model = ViTPipelineDef(
        image_size=img, patch_size=patch, dim=dim, depth=depth,
        heads=heads, num_classes=cfg.num_classes,
        interleave=interleave, pp_stages=pp if interleave > 1 else 0,
    )
    cfg = __import__("dataclasses").replace(cfg, image_size=img)
    m = microbatches or pp
    optimizer = SGD(momentum=0.9, weight_decay=1e-4)
    params, st = model.init(jax.random.PRNGKey(0))
    specs = model.pp_param_specs(mesh_lib.PIPE_AXIS)
    state = TrainState(
        params=mesh_lib.place_host_tree(mesh, params, specs),
        bn_state=mesh_lib.place_host_tree(mesh, st),
        opt_state=mesh_lib.place_host_tree(mesh, optimizer.init(params), specs),
        step=mesh_lib.place_host_tree(mesh, jnp.zeros((), jnp.int32)),
    )
    step = make_train_step(
        model.apply, optimizer, mesh, sync_bn=False,
        compute_dtype=jnp.bfloat16 if cfg.bf16 else jnp.float32,
        pp_axis=mesh_lib.PIPE_AXIS, param_specs=specs,
        model_kwargs={"n_microbatches": m} if microbatches else None,
    )
    batch = cfg.global_batch
    n_data = n // pp
    if (batch // n_data) % m:
        batch = n_data * m * max(1, batch // (n_data * m))
    rng = np.random.default_rng(0)
    images = mesh_lib.shard_batch(
        mesh, rng.normal(size=(batch, cfg.image_size, cfg.image_size, 3)).astype(np.float32)
    )
    labels = mesh_lib.shard_batch(
        mesh, rng.integers(0, cfg.num_classes, batch).astype(np.int32)
    )
    call = step.lower(state, images, labels, 0.1).compile()
    flops = _step_cost(call)["flops_per_step"]
    for _ in range(warmup):
        state, metrics = call(state, images, labels, 0.1)
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = call(state, images, labels, 0.1)
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    img_per_sec = batch * steps / dt
    return _stamped({
        "metric": (
            f"{cfg.name}_pp{pp}x{interleave}_m{m}"
            + ("_tiny" if dims == "tiny" else "")
            + "_train_throughput"
        ),
        "value": round(img_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
        "n_devices": n,
        "global_batch": batch,
        "pp_stages": pp,
        "pp_interleave": interleave,
        "pp_microbatches": m,
        "bubble_fraction": round(bubble_fraction(pp, m, interleave), 4),
        "step_ms": round(1000 * dt / steps, 2),
        "mfu": _mfu(flops, dt / steps, n),
        "goodput_frac": round(dt / (time.perf_counter() - t_bench0), 4),
    })


def _build_model(cfg: BenchConfig):
    from tpu_dist.nn import resnet18, resnet34, resnet50
    from tpu_dist.nn.resnet import resnet50_imagenet
    from tpu_dist.nn.vit import vit_b16

    builders = {
        "resnet18": resnet18, "resnet34": resnet34, "resnet50": resnet50,
        "resnet50_imagenet": lambda num_classes: resnet50_imagenet(
            num_classes, s2d_stem=cfg.s2d
        ),
        "vit_b16": lambda num_classes: vit_b16(num_classes, cfg.image_size),
    }
    return builders[cfg.model](num_classes=cfg.num_classes)


def run_ckpt(cfg: BenchConfig, warmup: int, mode: str, saves: int = 6) -> dict:
    """Sharded-checkpoint drill (``--ckpt``): how long does the STEP LOOP
    stay blocked per save?  ``sync`` pays uncommit + device→host snapshot
    + serialize + CRC32 + write + manifest commit inline;  ``async`` pays
    only uncommit + snapshot — the rest runs on the writer thread
    (``ckpt/checkpoint.py`` two-phase protocol).  A real compiled train
    step runs between saves so the async writer has compute to hide
    behind, and the drill proves the hidden work still happened: the
    drain is bounded-waited, the newest manifest is deep-verified
    (CRC32), and on the async path an injected EIO (``--fault_plan``
    ladder) MUST surface through the drain — the TD120 CLI probe; the
    caller exits 2 when ``ckpt_eio_probe`` comes back dead."""
    t_bench0 = time.perf_counter()
    import os  # noqa: PLC0415
    import shutil  # noqa: PLC0415
    import tempfile  # noqa: PLC0415

    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    from tpu_dist.ckpt import checkpoint as ckpt  # noqa: PLC0415
    from tpu_dist.comm import mesh as mesh_lib  # noqa: PLC0415
    from tpu_dist.resilience import faults  # noqa: PLC0415
    from tpu_dist.train.optim import SGD  # noqa: PLC0415
    from tpu_dist.train.state import TrainState  # noqa: PLC0415
    from tpu_dist.train.step import make_train_step  # noqa: PLC0415

    assert mode in ("sync", "async"), mode
    mesh = mesh_lib.data_parallel_mesh()
    n_dev = int(mesh.devices.size)
    batch = max(n_dev, (cfg.global_batch // n_dev) * n_dev)

    model = _build_model(cfg)
    optimizer = SGD(momentum=0.9, weight_decay=1e-4)
    params, bn_state = model.init(jax.random.PRNGKey(0))
    state = jax.device_put(
        TrainState.create(params, bn_state, optimizer), mesh_lib.replicated(mesh)
    )
    step = make_train_step(
        model.apply, optimizer, mesh, sync_bn=False,
        compute_dtype=jnp.bfloat16 if cfg.bf16 else jnp.float32,
    )
    rng = np.random.default_rng(0)
    images = mesh_lib.shard_batch(
        mesh,
        rng.normal(size=(batch, cfg.image_size, cfg.image_size, 3)).astype(np.float32),
    )
    labels = mesh_lib.shard_batch(
        mesh, rng.integers(0, cfg.num_classes, batch).astype(np.int32)
    )
    for _ in range(max(1, warmup)):
        state, _metrics = step(state, images, labels, 0.1)
    jax.block_until_ready(state.params)
    snap_bytes = ckpt.snapshot_sharded(state, 0).nbytes

    ckpt_dir = tempfile.mkdtemp(prefix=f"ckpt_bench_{mode}_")
    writer = ckpt.AsyncShardedCheckpointer() if mode == "async" else None
    blocked: list = []
    try:
        for i in range(saves):
            state, _metrics = step(state, images, labels, 0.1)
            jax.block_until_ready(state.params)
            # step boundary reached: from here to t1 is PURE save blocking
            t0 = time.perf_counter()
            if writer is None:
                ckpt.save_sharded(ckpt_dir, state, epoch=i)
            else:
                writer.save(ckpt_dir, state, epoch=i)
            blocked.append(time.perf_counter() - t0)
        t_drain0 = time.perf_counter()
        if writer is not None and not writer.close(timeout=600.0):
            raise RuntimeError("ckpt drill: async writer failed to drain")
        drain_ms = round(1000 * (time.perf_counter() - t_drain0), 3)

        latest = ckpt.latest_sharded_checkpoint(ckpt_dir)
        if latest is None or latest[1] != saves - 1:
            raise RuntimeError(
                f"ckpt drill: expected committed epoch {saves - 1}, "
                f"found {latest!r}"
            )
        ckpt.verify_sharded(latest[0], deep=True)  # raises on corruption

        eio_probe = None
        if mode == "async":
            # TD120 probe: arm an EIO on the next shard write and prove the
            # background error SURFACES at the drain — a clean probe means
            # async writes could silently lose checkpoints.
            probe_dir = os.path.join(ckpt_dir, "eio_probe")
            faults.configure("ckpt_write@call=1")
            probe_writer = ckpt.AsyncShardedCheckpointer()
            try:
                probe_writer.save(probe_dir, state, epoch=saves)
                probe_writer.wait(timeout=600.0)
                eio_probe = "dead"
            except OSError:
                eio_probe = "caught"
            finally:
                faults.clear()
                try:
                    probe_writer.close(timeout=60.0)
                except OSError:
                    pass  # the probe's own injected error draining out
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    out = {
        # no "value": blocked ms is lower-is-better; compare gates the
        # registry-declared ckpt_blocked_ms field instead (obs/compare.py)
        "metric": f"sharded_ckpt_{mode}",
        "unit": "ms blocked per save",
        "ckpt_mode": mode,
        "ckpt_blocked_ms": round(1000 * sum(blocked) / len(blocked), 3),
        "ckpt_blocked_ms_max": round(1000 * max(blocked), 3),
        "ckpt_saves": saves,
        "ckpt_snapshot_bytes": int(snap_bytes),
        "n_devices": n_dev,
        "wall_s": round(time.perf_counter() - t_bench0, 2),
    }
    if mode == "async":
        out["ckpt_drain_ms"] = drain_ms
        out["ckpt_eio_probe"] = eio_probe
    return _stamped(out)


def _require_chip() -> None:
    """No TPU (or one the peak table has no row for) → one line on stderr
    and exit 3, before anything is measured or printed to stdout."""
    import sys

    import jax

    try:
        _costmodel().require_chip_row(jax.devices()[0])
    except RuntimeError as e:
        print(f"bench: {e}; refusing to measure", file=sys.stderr, flush=True)
        sys.exit(3)


def run_serve(
    cfg: BenchConfig, n_requests: int, *, max_batch: int = 8,
    tiny: bool = False,
) -> dict:
    """Serving micro-bench (``--serve``): drive the continuous-batching
    engine (``tpu_dist/serve``) with a bursty deterministic arrival
    pattern on the REAL clock and report the serving axis of the bench
    trajectory — ``requests_per_s`` (the headline ``value``),
    ``latency_p50_ms``/``latency_p99_ms`` (histogram upper bounds) and
    ``batch_occupancy`` — with the standard capture fingerprint, so a
    stale re-emission of a serving number is auto-flagged exactly like
    a training one. ``tiny`` swaps in a narrow ResNet for CPU-emulation
    validation (the measurement shape is the config's model)."""
    t0 = time.perf_counter()
    from tpu_dist.nn import resnet18, resnet34, resnet50
    from tpu_dist.obs import counters as counters_lib
    from tpu_dist.serve.engine import ServingEngine

    counters_lib.reset()
    if tiny:
        from tpu_dist.serve.drill import _drill_model

        model, image, classes, name = _drill_model(), 16, 10, "tiny"
    else:
        models = {
            "resnet18": resnet18, "resnet34": resnet34, "resnet50": resnet50,
        }
        if cfg.model not in models:
            raise ValueError(
                f"--serve benches the dense image models, got {cfg.model!r}"
            )
        model = models[cfg.model](num_classes=cfg.num_classes)
        image, classes, name = cfg.image_size, cfg.num_classes, cfg.model
    import jax

    params, bn_state = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, bn_state, max_batch=max_batch)
    engine.warmup((image, image, 3))
    rng = np.random.default_rng(0)
    payloads = rng.standard_normal(
        (min(n_requests, 64), image, image, 3)
    ).astype(np.float32)
    t_meas = time.perf_counter()
    submitted = 0
    done = 0
    burst_idx = 0
    while done < n_requests:
        if submitted < n_requests:
            # bursty arrivals: alternate 3- and 7-request bursts so the
            # batcher genuinely exercises several buckets
            burst = (3, 7)[burst_idx % 2]
            burst_idx += 1
            for _ in range(min(burst, n_requests - submitted)):
                engine.submit(payloads[submitted % len(payloads)],
                              id=submitted)
                submitted += 1
        done += len(engine.pump())
    meas_s = max(time.perf_counter() - t_meas, 1e-9)
    stats = engine.stats
    total_s = time.perf_counter() - t0
    return _stamped({
        "metric": f"serve_{name}_throughput",
        "value": round(done / meas_s, 1),
        "unit": "requests/sec",
        "requests_per_s": round(done / meas_s, 1),
        "latency_p50_ms": round((stats.total.quantile_bound(0.5) or 0) * 1e3, 3),
        "latency_p99_ms": round((stats.total.quantile_bound(0.99) or 0) * 1e3, 3),
        "ttfb_p99_ms": round((stats.ttfb.quantile_bound(0.99) or 0) * 1e3, 3),
        "batch_occupancy": round(stats.batch_occupancy() or 0.0, 4),
        "requests": done,
        "batches": stats.batches,
        "max_batch": max_batch,
        "image_size": image,
        "num_classes": classes,
        "retraces": counters_lib.get("compile.retraces"),
        "goodput_frac": round(meas_s / total_s, 4),
    })


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="resnet18_cifar100", choices=sorted(CONFIGS))
    p.add_argument("--all", action="store_true", help="run every config (one line each)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--warmup", type=int, default=10)
    p.add_argument(
        "--batch_size", type=int, default=0,
        help="override the config's global batch (0 = config default); "
             "probing the throughput/MFU-vs-batch curve without editing "
             "CONFIGS",
    )
    p.add_argument(
        "--table", action="store_true",
        help="emit the reference README's comparison table (markdown), one "
             "row per training mode, measured on the visible devices",
    )
    p.add_argument(
        "--pp", type=int, default=0,
        help="pipeline-parallel bench: split ViT-B/16 into N stages over a "
             "(data x pipe) mesh; reports throughput + bubble_fraction "
             "(run with CPU device-count emulation on single-chip hosts)",
    )
    p.add_argument("--pp_interleave", type=int, default=1)
    p.add_argument(
        "--pp_dims", choices=("b16", "tiny"), default="b16",
        help="tiny swaps in a small ViT for schedule validation on CPU "
             "emulation; b16 is the measurement shape",
    )
    p.add_argument(
        "--pp_microbatches", type=int, default=0,
        help="microbatches M >= stages (0 = one per stage); larger M "
             "shrinks the bubble (S-1)/(vM+S-1)",
    )
    p.add_argument(
        "--attn", type=int, default=0, metavar="S",
        help="long-sequence attention micro-bench at sequence length S: "
             "Pallas flash kernel vs the XLA path, fwd+bwd (the "
             "long-context showcase; try 1024/4096/16384)",
    )
    p.add_argument(
        "--attn_all", action="store_true",
        help="run the attention micro-bench at S=1024, 4096, 16384 "
             "(one line each)",
    )
    p.add_argument("--attn_batch", type=int, default=0,
                   help="batch for --attn (0 = ~32k tokens/step)")
    p.add_argument(
        "--profile_dir", default="",
        help="capture an XLA/TPU profile of the measured steps to this dir "
             "(TensorBoard profile tab; single-config mode only)",
    )
    p.add_argument("--causal", action="store_true",
                   help="causal masking for --attn")
    p.add_argument(
        "--grad_compression",
        choices=("none", "bf16", "int8", "int8_ef", "sweep"),
        default="none",
        help="gradient wire format for the measured step; 'sweep' runs the "
             "config once per mode (one JSON line each) reporting "
             "wire_bytes_per_step from the static jaxpr audit (works on "
             "CPU emulation) alongside measured throughput",
    )
    p.add_argument(
        "--ckpt",
        choices=("off", "sync", "async", "sweep"),
        default="off",
        help="sharded-checkpoint drill: measure step-loop blocking time "
             "per save (ckpt_blocked_ms) for the synchronous vs the "
             "snapshot-then-write (--async_ckpt) composition; 'sweep' runs "
             "both, prints the blocking ratio, and exits 2 if the "
             "injected-EIO probe through the async drain comes back dead "
             "(the TD120 CLI gate)",
    )
    p.add_argument(
        "--serve", action="store_true",
        help="serving micro-bench: drive the continuous-batching engine "
             "(tpu_dist/serve) with bursty arrivals and emit "
             "requests_per_s / latency_p50_ms / latency_p99_ms / "
             "batch_occupancy as one fingerprinted bench record — the "
             "serving axis of the bench trajectory",
    )
    p.add_argument("--serve_requests", type=int, default=256,
                   help="requests driven through the engine (--serve)")
    p.add_argument("--serve_max_batch", type=int, default=8,
                   help="bucket-ladder top (--serve; power of two)")
    p.add_argument(
        "--serve_tiny", action="store_true",
        help="narrow-ResNet serving bench for CPU-emulation validation "
             "(the measurement shape is the config's model)",
    )
    p.add_argument(
        "--scaling", action="store_true",
        help="run the config on 1,2,4,...,N-device meshes and report "
             "scaling efficiency (BASELINE's 1→8→32 chip metric; limited "
             "by visible devices)",
    )
    p.add_argument(
        "--archive", default=None, metavar="PATH",
        help="self-ingest every emitted record into this longitudinal "
             "archive at exit (python -m tpu_dist.obs archive / trend; "
             "never-dies — an archive failure is counted to stderr, "
             "not fatal to the bench)",
    )
    args = p.parse_args()
    if args.archive:
        import atexit

        # normal exits (and sys.exit) archive whatever _stamped emitted
        atexit.register(_self_ingest, args.archive)
    if args.batch_size:
        import dataclasses

        CONFIGS.update(
            {
                name: dataclasses.replace(c, global_batch=args.batch_size)
                for name, c in CONFIGS.items()
            }
        )

    import sys

    import jax

    from tpu_dist import compile_cache

    compile_cache.enable()
    _require_chip()
    if args.ckpt != "off" and not args.table:
        modes = ("sync", "async") if args.ckpt == "sweep" else (args.ckpt,)
        recs = {}
        for m in modes:
            recs[m] = run_ckpt(CONFIGS[args.config], args.warmup, m)
            print(json.dumps(recs[m]), flush=True)
        if args.ckpt == "sweep":
            ratio = recs["sync"]["ckpt_blocked_ms"] / max(
                recs["async"]["ckpt_blocked_ms"], 1e-9
            )
            print(json.dumps(_stamped({
                "metric": "sharded_ckpt_blocking_ratio",
                "value": round(ratio, 2),
                "unit": "x (sync blocked / async blocked)",
            })), flush=True)
        dead = [m for m, r in recs.items() if r.get("ckpt_eio_probe") == "dead"]
        if dead:
            print(
                "bench --ckpt: injected EIO came back CLEAN through the "
                "async drain — the TD120 fault detector is dead",
                file=sys.stderr,
            )
            sys.exit(2)
        return
    if args.serve:
        print(json.dumps(run_serve(
            CONFIGS[args.config], args.serve_requests,
            max_batch=args.serve_max_batch, tiny=args.serve_tiny,
        )), flush=True)
        return
    if args.attn or args.attn_all:
        lengths = (1024, 4096, 16384) if args.attn_all else (args.attn,)
        for s in lengths:
            print(json.dumps(run_attn(
                s, args.steps, args.warmup,
                batch=args.attn_batch, causal=args.causal,
            )), flush=True)
        return
    if args.pp:
        cfg_name = args.config if args.config.startswith("vit") else "vit_b16_imagenet"
        print(json.dumps(run_pp(
            CONFIGS[cfg_name], args.steps, args.warmup,
            args.pp, args.pp_interleave, args.pp_microbatches,
            dims=args.pp_dims,
        )))
        return
    if args.table:
        # reference README comparison-table parity (README.md:59-77): one
        # row per training mode, same model/dataset, epoch seconds
        rows = [
            ("dataparallel (DP ≡ DDP on TPU)", "resnet18_cifar100_fp32"),
            ("distributed + bf16 (apex path)", "resnet18_cifar100"),
            ("grad accumulation ×4", "resnet18_cifar100_ga4"),
            ("fused epoch (device-resident)", "resnet18_cifar100_fused"),
        ]
        from tpu_dist.obs.memory import fmt_bytes

        print("| mode | sec/epoch | images/sec | MFU | goodput | peak HBM "
              "| ckpt blocked/save | vs 4x2080Ti DDP+apex |")
        print("|---|---|---|---|---|---|---|---|")
        for label, name in rows:
            out = run(CONFIGS[name], args.steps, args.warmup)
            mfu = out.get("mfu")
            gp = out.get("goodput_frac")
            # XLA's static per-executable accounting (memory_analysis) —
            # already in every bench record
            hbm = out.get("peak_hbm_bytes")
            # checkpoint-blocking column: a short sharded-save drill per
            # row when --ckpt is given ('sweep' shows sync→async, the
            # two-phase protocol's before/after); 'n/a' keeps the default
            # table invocation's cost unchanged
            if args.ckpt == "off":
                ck = "n/a"
            elif args.ckpt == "sweep":
                cs = run_ckpt(CONFIGS[name], 2, "sync", saves=3)
                ca = run_ckpt(CONFIGS[name], 2, "async", saves=3)
                ck = (f"{cs['ckpt_blocked_ms']:.0f}→"
                      f"{ca['ckpt_blocked_ms']:.0f} ms")
            else:
                cr = run_ckpt(CONFIGS[name], 2, args.ckpt, saves=3)
                ck = f"{cr['ckpt_blocked_ms']:.0f} ms ({args.ckpt})"
            print(
                f"| {label} | {out['sec_per_epoch']} | {out['value']} "
                f"| {f'{mfu:.1%}' if mfu is not None else 'n/a'} "
                f"| {f'{gp:.1%}' if gp is not None else 'n/a'} "
                f"| {fmt_bytes(hbm) if hbm is not None else 'n/a'} "
                f"| {ck} "
                f"| {out['vs_baseline']}x |"
            )
        return
    if args.grad_compression == "sweep":
        # per-mode wire bytes (static, exact) + throughput, one line each —
        # the measured counterpart of the TD104 audit ratios
        for mode in ("none", "bf16", "int8", "int8_ef"):
            print(json.dumps(run(
                CONFIGS[args.config], args.steps, args.warmup,
                grad_compression=mode,
            )), flush=True)
        return
    if args.scaling:
        n = len(jax.devices())
        sizes = [s for s in (1, 2, 4, 8, 16, 32) if s <= n]
        base = None
        for s in sizes:
            out = run(CONFIGS[args.config], args.steps, args.warmup, n_devices=s)
            if base is None:
                base = out["value"]
            out["scaling_efficiency"] = round(out["value"] / (base * s), 3)
            print(json.dumps(out))
    elif args.all:
        failed = []
        for name in sorted(CONFIGS):
            try:
                print(json.dumps(run(CONFIGS[name], args.steps, args.warmup)),
                      flush=True)
            except Exception as e:  # record it, keep sweeping, fail at the end
                failed.append(name)
                print(json.dumps({
                    "metric": f"{name}_train_throughput", "value": None,
                    "unit": "images/sec",
                    "error": f"{type(e).__name__}: {(str(e).splitlines() or [''])[0][:200]}",
                }), flush=True)
        if failed:
            print(f"bench --all: {len(failed)} config(s) failed: "
                  f"{', '.join(failed)}", file=sys.stderr)
            sys.exit(1)
    else:
        print(json.dumps(run(
            CONFIGS[args.config], args.steps, args.warmup,
            profile_dir=args.profile_dir or None,
            grad_compression=args.grad_compression,
        )))


if __name__ == "__main__":
    main()
