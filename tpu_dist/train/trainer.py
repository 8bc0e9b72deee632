"""The trainer — TPU-native ``main_worker`` (SURVEY §1 L3).

One trainer replaces all six reference scripts: on TPU, DP and DDP collapse
into "one process per host drives all local chips, params replicated, grads
pmean-ed" (SURVEY §7 design stance), so the reference's script matrix
becomes config flags:

==============================================  =============================
reference script                                 config
==============================================  =============================
``dataparallel.py`` / ``distributed{_mp}.py``    defaults
``dataparallel_apex.py`` / ``distributed_apex``  ``bf16=True``
``distributed_gradient_accumulation.py``         ``grad_accu_steps=K``
SyncBN on/off (``distributed.py:59``)            ``sync_bn``
==============================================  =============================

Preserved reference behaviors (SURVEY §7 fidelity list): per-replica batch =
global/ N (``distributed.py:67``), epoch-seeded shuffle via ``set_epoch``
(``:81``), per-rank+epoch augmentation seeding (``distributed_mp.py:29-39``),
rank-0-only output, MultiStepLR/SGD hyperparameters, per-step metric
reduction and log line (``:104-111``), epoch wall-time print (``:113-115``),
per-epoch distributed validation. Deliberately dropped: the per-step
``dist.barrier()`` (ordering is XLA dataflow now, SURVEY §5).
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import numpy as np
import jax.numpy as jnp
from tpu_dist import ckpt as ckpt_lib
from tpu_dist import compile_cache
from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.config import TrainConfig
from tpu_dist.data import (
    DataLoader,
    DistributedSampler,
    load_cifar10,
    load_cifar100,
    synthetic_cifar,
)
from tpu_dist.evaluation import validate
from tpu_dist.metrics import AverageMeter, rank0_print
from tpu_dist.obs.profile import StepTimer
from tpu_dist.nn import resnet18, resnet34, resnet50
from tpu_dist.obs import costmodel as costmodel_lib
from tpu_dist.obs import counters as counters_lib
from tpu_dist.obs import goodput as goodput_lib
from tpu_dist.obs import spans as spans_lib
from tpu_dist.resilience import faults, preemption
from tpu_dist.resilience.preemption import PreemptedError
from tpu_dist.train.optim import SGD, cosine_lr, multistep_lr
from tpu_dist.train.state import TrainState
from tpu_dist.train.step import make_eval_step, make_train_step

_MODELS = {"resnet18": resnet18, "resnet34": resnet34, "resnet50": resnet50}


class TrainingDivergedError(RuntimeError):
    """Raised by the NaN guard — the failure-detection subsystem the
    reference lacks entirely (SURVEY §5: no failure detection/recovery).
    Catch it and restore from ``ckpt_dir`` to implement auto-recovery."""


def _fetch_metrics(metrics) -> dict:
    """ONE device→host transfer for the whole metrics dict. A per-key
    ``float(v)`` comprehension issues one blocking D2H round-trip per
    scalar; ``jax.device_get`` fetches the tree in a single call, and the
    NaN guard / log line / history all reuse the same host copy."""
    return {k: float(v) for k, v in jax.device_get(metrics).items()}


def register_model(name: str, factory) -> None:
    """Extend the model zoo (``factory(num_classes=...) -> model`` with
    ``init``/``apply``). Lets users swap models the way the reference
    suggests swapping ``utils/model.py`` (BASELINE north star's ViT config).
    """
    _MODELS[name] = factory


def build_model(cfg: TrainConfig):
    try:
        from tpu_dist.nn.vit import vit_b16, vit_s16, vit_tiny  # noqa: PLC0415

        _MODELS.setdefault("vit_b16", vit_b16)
        _MODELS.setdefault("vit_s16", vit_s16)
        _MODELS.setdefault("vit_tiny", vit_tiny)

        from tpu_dist.nn.vit_moe import vit_moe_tiny  # noqa: PLC0415

        _MODELS.setdefault("vit_moe_tiny", vit_moe_tiny)

        from tpu_dist.nn.vit_pp import vit_pp_tiny  # noqa: PLC0415

        _MODELS.setdefault("vit_pp_tiny", vit_pp_tiny)

        from tpu_dist.nn.resnet import resnet50_imagenet  # noqa: PLC0415

        _MODELS.setdefault("resnet50_imagenet", resnet50_imagenet)

        from tpu_dist.nn.nemotron_h import (  # noqa: PLC0415
            lfm2_24b_a2b_share,
            lfm2_moe_tiny,
            nemotron3_nano_share,
            nemotron_h_tiny,
        )

        _MODELS.setdefault("nemotron3_nano_share", nemotron3_nano_share)
        _MODELS.setdefault("nemotron_h_tiny", nemotron_h_tiny)
        _MODELS.setdefault("lfm2_24b_a2b_share", lfm2_24b_a2b_share)
        _MODELS.setdefault("lfm2_moe_tiny", lfm2_moe_tiny)
    except ImportError:
        pass
    if cfg.model not in _MODELS:
        raise ValueError(f"unknown model {cfg.model!r}; have {sorted(_MODELS)}")
    return _MODELS[cfg.model](num_classes=cfg.num_classes)


class Trainer:
    def __init__(self, cfg: TrainConfig, mesh=None):
        self.cfg = cfg
        # the telemetry counter registry is process-global and a "run" is
        # one Trainer's lifetime (run_id is stamped per construction, so
        # repeated fit() calls on one instance share it): start the
        # registry fresh here so a second Trainer in the same process
        # (tests, sweep drivers) doesn't report the previous run's totals
        # under its fresh run_id — and so the restore ladder's counters
        # (which run during THIS construction, below) attribute to this run
        counters_lib.reset()
        # the goodput ledger's wall-clock book opens NOW: construction —
        # the resume restore ladder included — is part of the run it
        # accounts, and every second from here to fit()'s exit lands in
        # exactly one bucket (obs/goodput.py)
        self._goodput = goodput_lib.GoodputLedger()
        # process-lifetime XLA compile-time accounting (compile.seconds):
        # idempotent, host-side, feeds the registry just reset above AND
        # the ledger's compile bucket (per-epoch counter deltas)
        costmodel_lib.install_compile_listener()
        if cfg.compile_cache_dir:
            # persistent XLA compile cache: a rerun of the same config
            # loads compiled programs instead of recompiling. Off unless
            # asked for — the CLI entry point asks (cli/train.py).
            compile_cache.enable(cfg.compile_cache_dir)
        mesh_lib.initialize_distributed(
            coordinator_address=cfg.coordinator_address if cfg.num_processes else None,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id,
        )
        if cfg.ckpt_io_retries < 0:
            raise ValueError(
                f"ckpt_io_retries must be >= 0, got {cfg.ckpt_io_retries}"
            )
        # transient-write retry ladder for every checkpoint file write
        # (process-global module state, same posture as compile_cache_dir)
        ckpt_lib.set_io_retries(cfg.ckpt_io_retries)
        # chaos harness: install the config/env fault plan (clears any plan
        # a previous Trainer in this process installed — a resumed run
        # without --fault_plan must not replay the crashed run's faults);
        # raises FaultPlanError on a malformed spec before training starts
        plan = faults.configure(cfg.fault_plan)
        if plan is not None and cfg.fused_epoch:
            stepwise = sorted(
                {c.site for c in plan.clauses} & faults.STEPWISE_SITES
            )
            if stepwise:
                raise ValueError(
                    f"--fault_plan sites {stepwise} act at the step/batch "
                    "grain, which --fused_epoch compiles away (the whole "
                    "epoch is one jit call and the streaming loader is "
                    "bypassed) — they would silently never fire. Use "
                    "ckpt_write/ckpt_corrupt clauses, or drop --fused_epoch "
                    "for chaos runs (refusing to silently ignore the plan)"
                )
        # --sharded_ckpt + --async_ckpt compose (snapshot-then-write): the
        # step loop blocks only for the device→host snapshot; serialization,
        # CRC, and the manifest commit run on the background writer, whose
        # commit barrier is filesystem-based — a jax collective never runs
        # off the main thread (ckpt/checkpoint.py::AsyncShardedCheckpointer,
        # docs/checkpointing.md "Two-phase sharded saves")
        # triggered on-device profiling (obs/profile.py): both specs are
        # validated HERE, before any model/data work, so a typo fails in
        # milliseconds rather than after the loaders built
        from tpu_dist.obs import profile as profile_lib  # noqa: PLC0415

        self._profile_triggers = profile_lib.parse_trigger(cfg.profile_trigger)
        manual_profile = profile_lib.parse_steps(cfg.profile_steps)
        self._profiler = None
        self._global_step = 0  # run-global step index (--profile_steps grid)
        if self._profile_triggers or manual_profile:
            if not cfg.profile_dir:
                raise ValueError(
                    "--profile_trigger/--profile_steps capture on-device "
                    "traces and need --profile_dir for the output "
                    "(refusing to silently ignore the flags)"
                )
            if cfg.fused_epoch:
                raise ValueError(
                    "--profile_trigger/--profile_steps need the per-step "
                    "grain; --fused_epoch compiles the epoch into one "
                    "call with no step boundary to open/close a capture "
                    "window at (use --profile_dir alone for the epoch-0 "
                    "blanket trace)"
                )
            import os as _os  # noqa: PLC0415

            out = (
                _os.path.join(cfg.profile_dir, f"host{mesh_lib.process_index()}")
                if mesh_lib.process_count() > 1 else cfg.profile_dir
            )
            # ctor validates window/cooldown/cap before training starts.
            # Created on EVERY process: anomaly/retrace triggers arm it on
            # rank 0 only, a straggler flag arms it on the flagged host —
            # the one whose timeline explains the skew.
            self._profiler = profile_lib.TriggeredProfiler(
                out,
                window_steps=cfg.profile_window,
                cooldown_steps=cfg.profile_cooldown,
                max_captures=cfg.profile_max_captures,
                manual_range=manual_profile,
            )
        # live telemetry (obs/export.py, obs/alerts.py): both specs are
        # validated HERE too — a bad rule file or port fails before any
        # model/data work, same posture as the profiler specs above
        if cfg.metrics_port < 0 or cfg.metrics_port > 65535:
            raise ValueError(
                f"metrics_port must be 0 (off) or a valid TCP port, got "
                f"{cfg.metrics_port}"
            )
        self._alert_rule_list = None
        if cfg.alert_rules:
            from tpu_dist.obs import alerts as alerts_lib  # noqa: PLC0415

            # raises on a malformed spec / unknown builtin / dup names
            self._alert_rule_list = alerts_lib.load_rules(cfg.alert_rules)
        if cfg.pp_interleave < 1:
            raise ValueError(f"pp_interleave must be >= 1, got {cfg.pp_interleave}")
        if cfg.pp_interleave > 1 and cfg.pp <= 1:
            raise ValueError(
                "pp_interleave > 1 has no effect without pp > 1 — set --pp "
                "to the stage count (refusing to silently ignore the flag)"
            )
        combined = sum(w > 1 for w in (cfg.sp, cfg.tp, cfg.ep, cfg.pp))
        if combined > 1 and not (
            combined == 2 and cfg.tp > 1 and (cfg.sp > 1 or cfg.pp > 1)
        ):
            raise ValueError(
                "only sp+tp (3-D DPxTPxSP) and pp+tp (Megatron DPxPPxTP) "
                "may be combined; other sp/tp/ep/pp combinations are not "
                "supported yet"
            )
        if mesh is not None:
            self.mesh = mesh
        elif cfg.sp > 1 and cfg.tp > 1:
            n = len(jax.devices())
            ways = cfg.sp * cfg.tp
            if n % ways:
                raise ValueError(f"{n} devices not divisible by tp*sp={ways}")
            self.mesh = mesh_lib.device_mesh(
                [n // ways, cfg.tp, cfg.sp],
                [mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS, mesh_lib.SEQ_AXIS],
            )
        elif cfg.pp > 1 and cfg.tp > 1:
            # Megatron layout: tp innermost (adjacent devices — ICI-local
            # psums every block), pipe next (nearest-neighbor ppermute ring),
            # data outermost
            n = len(jax.devices())
            ways = cfg.pp * cfg.tp
            if n % ways:
                raise ValueError(f"{n} devices not divisible by pp*tp={ways}")
            self.mesh = mesh_lib.device_mesh(
                [n // ways, cfg.pp, cfg.tp],
                [mesh_lib.DATA_AXIS, mesh_lib.PIPE_AXIS, mesh_lib.MODEL_AXIS],
            )
        elif cfg.sp > 1 or cfg.tp > 1 or cfg.ep > 1 or cfg.pp > 1:
            ways = max(cfg.sp, cfg.tp, cfg.ep, cfg.pp)
            second = (
                mesh_lib.SEQ_AXIS if cfg.sp > 1
                else mesh_lib.MODEL_AXIS if cfg.tp > 1
                else mesh_lib.EXPERT_AXIS if cfg.ep > 1
                else mesh_lib.PIPE_AXIS
            )
            n = len(jax.devices())
            if n % ways:
                raise ValueError(f"{n} devices not divisible by sp/tp/ep/pp={ways}")
            self.mesh = mesh_lib.device_mesh(
                [n // ways, ways], [mesh_lib.DATA_AXIS, second]
            )
        else:
            self.mesh = mesh_lib.data_parallel_mesh()
        self._check_mesh_host_layout()
        # data-parallel width (batch divides over this, not over SP ways)
        self.n_data = int(self.mesh.shape[mesh_lib.DATA_AXIS])
        self.n_devices = int(self.mesh.devices.size)
        from tpu_dist.nn.attention import (  # noqa: PLC0415
            set_default_attention_impl,
        )

        # set BOTH directions: the default is process-global, and a later
        # Trainer in the same process must not inherit a stale 'flash'
        set_default_attention_impl(self._attn_impl(cfg))
        self.model = build_model(cfg)
        # a model with its own loss takes token ids and per-position targets
        # (nn/nemotron_h.py); the steps call its `loss` where it has one
        self._token_model = hasattr(self.model, "loss")
        if self._token_model and (
            cfg.fsdp or cfg.fused_epoch or cfg.dataset != "synthetic_tokens"
        ):
            raise ValueError(
                f"model {cfg.model!r} takes token ids: --dataset synthetic_tokens, "
                "the plain data-parallel step (no --fsdp, no --fused_epoch)"
            )
        if cfg.sp_mode not in ("ring", "ulysses"):
            raise ValueError(
                f"sp_mode must be 'ring' or 'ulysses', got {cfg.sp_mode!r}"
            )
        if cfg.sp > 1:
            import inspect  # noqa: PLC0415

            if "seq_axis" not in inspect.signature(self.model.apply).parameters:
                raise ValueError(
                    f"model {cfg.model!r} does not support sequence parallelism "
                    f"(no seq_axis in apply); use a ViT model or sp=1"
                )
            if cfg.sp_mode == "ulysses":
                heads = getattr(self.model, "heads", None)
                # under sp x tp the attention sees heads/tp LOCAL heads
                # (column-sharded qkv) — validate the count it will see
                local_heads = (
                    heads // cfg.tp if heads is not None and cfg.tp > 1 else heads
                )
                if local_heads is not None and local_heads % cfg.sp:
                    raise ValueError(
                        f"sp_mode='ulysses' needs per-shard heads "
                        f"({local_heads}{f' = {heads}/tp' if cfg.tp > 1 else ''}) "
                        f"divisible by sp ({cfg.sp}); use sp_mode='ring'"
                    )
                if "sp_mode" not in inspect.signature(self.model.apply).parameters:
                    raise ValueError(
                        f"model {cfg.model!r} does not support sp_mode "
                        f"(ulysses); use a ViT model or sp_mode='ring'"
                    )
            if cfg.fused_epoch:
                raise ValueError("sp > 1 is not supported with fused_epoch")
            n_tokens = getattr(self.model, "n_patches", None)
            if n_tokens is not None and n_tokens % cfg.sp:
                raise ValueError(
                    f"model has {n_tokens} patch tokens, not divisible by "
                    f"sp={cfg.sp} — tokens would be dropped"
                )
            if cfg.batch_size % (self.n_data * cfg.sp):
                raise ValueError(
                    f"with sp>1, batch_size {cfg.batch_size} must also divide "
                    f"over the {self.n_data * cfg.sp} data x seq devices for "
                    f"evaluation sharding"
                )
        self._param_specs = None
        self._fsdp_specs = None
        if cfg.fsdp:
            if cfg.sp > 1 or cfg.ep > 1 or cfg.pp > 1:
                raise ValueError(
                    "fsdp composes with --tp (GSPMD spec overlay) but not "
                    "with sp/ep/pp: the ring/all_to_all/pipeline engines "
                    "are shard_map programs, and a leaf cannot be owned by "
                    "both a hand-written collective schedule and the "
                    "GSPMD partitioner"
                )
            if cfg.fused_epoch or cfg.shard_weight_update:
                raise ValueError(
                    "fsdp is incompatible with fused_epoch / zero1 (fsdp "
                    "supersedes ZeRO-1: momentum AND params are sharded)"
                )
            if cfg.fused_optimizer:
                raise ValueError(
                    "fsdp uses the plain SGD update (XLA fuses it into the "
                    "sharded program); fused_optimizer is shard_map-path only"
                )
            if not cfg.sync_bn:
                # not an error: BN-free models (ViT) legitimately pass
                # sync_bn=False; for BN models the flag simply cannot take
                # effect under GSPMD's global-batch semantics
                rank0_print(
                    "WARNING: --no_sync_bn has no effect under --fsdp — "
                    "BatchNorm statistics are global-batch (SyncBN) by "
                    "construction in the GSPMD engine"
                )
            if cfg.debug_replica_check:
                raise ValueError(
                    "debug_replica_check asserts replicated params; under "
                    "fsdp params are sharded by design"
                )
            if cfg.grad_compression != "none":
                rank0_print(
                    "WARNING: --grad_compression has no effect under --fsdp "
                    "— the engine's collectives (including the gradient "
                    "reduce-scatters the bf16/int8 wire formats would "
                    "compress) are GSPMD-inserted from sharding specs, not "
                    "hookable per-tensor (docs/compression.md)"
                )
            if cfg.flash_attention:
                raise ValueError(
                    "--fsdp with --flash_attention is not supported: the "
                    "Pallas kernel runs inside the GSPMD-partitioned jit "
                    "(no shard_map), where it has no SPMD partitioning "
                    "rule — XLA would replicate or fail to compile. Use "
                    "the default XLA attention under fsdp"
                )
        if cfg.tp > 1:
            import inspect  # noqa: PLC0415

            if "tp_axis" not in inspect.signature(self.model.apply).parameters:
                raise ValueError(
                    f"model {cfg.model!r} does not support tensor parallelism "
                    f"(no tp_axis in apply); use a ViT model or tp=1"
                )
            heads = getattr(self.model, "heads", None)
            if heads is not None and heads % cfg.tp:
                raise ValueError(f"{heads} heads not divisible by tp={cfg.tp}")
            if cfg.fused_epoch or cfg.shard_weight_update:
                raise ValueError(
                    "tp > 1 is incompatible with fused_epoch / zero1 "
                    "(grad_clip_norm composes — shard-aware norm in step.py)"
                )
            if cfg.pp <= 1:  # under PP×TP the pp branch sets combined specs
                self._param_specs = self.model.tp_param_specs(mesh_lib.MODEL_AXIS)
        from tpu_dist.train.step import QUANTIZED_MODES  # noqa: PLC0415

        if (
            cfg.grad_compression in QUANTIZED_MODES
            and not cfg.fsdp
            and (cfg.sp > 1 or cfg.tp > 1 or cfg.ep > 1 or cfg.pp > 1)
        ):
            # same wall as make_train_step, caught at the config layer: the
            # quantized two-stage reduce assumes one data axis over a
            # replicated param tree (docs/compression.md)
            raise ValueError(
                f"grad_compression={cfg.grad_compression!r} is scoped to "
                "the plain data-parallel, fused-epoch, and ZeRO-1 paths — "
                "it cannot combine with sp/tp/ep/pp (use "
                "--grad_compression bf16 there)"
            )
        if cfg.moe_top_k < 1:
            raise ValueError(f"moe_top_k must be >= 1, got {cfg.moe_top_k}")
        if cfg.moe_top_k > 1:
            import dataclasses as _dc  # noqa: PLC0415

            if not (_dc.is_dataclass(self.model) and hasattr(self.model, "top_k")):
                raise ValueError(
                    f"model {cfg.model!r} has no MoE router (no top_k field) "
                    f"— --moe_top_k applies to vit_moe_* models"
                )
            if cfg.moe_top_k > self.model.n_experts:
                raise ValueError(
                    f"moe_top_k={cfg.moe_top_k} exceeds the model's "
                    f"{self.model.n_experts} experts"
                )
            self.model = _dc.replace(self.model, top_k=cfg.moe_top_k)
        if cfg.ep > 1:
            import inspect  # noqa: PLC0415

            if "ep_axis" not in inspect.signature(self.model.apply).parameters:
                raise ValueError(
                    f"model {cfg.model!r} does not support expert parallelism "
                    f"(no ep_axis in apply); use a MoE model or ep=1"
                )
            n_exp = getattr(self.model, "n_experts", None)
            if n_exp is not None and n_exp % cfg.ep:
                raise ValueError(f"{n_exp} experts not divisible by ep={cfg.ep}")
            if cfg.fused_epoch or cfg.shard_weight_update:
                raise ValueError(
                    "ep > 1 is incompatible with fused_epoch / zero1 "
                    "(grad_clip_norm composes — shard-aware norm in step.py)"
                )
            if cfg.batch_size % self.n_devices:
                raise ValueError(
                    f"with ep>1, batch_size {cfg.batch_size} must divide over "
                    f"all {self.n_devices} devices (the expert axis carries data)"
                )
            self._param_specs = self.model.ep_param_specs(mesh_lib.EXPERT_AXIS)
        if cfg.pp > 1:
            import inspect  # noqa: PLC0415

            if "pp_axis" not in inspect.signature(self.model.apply).parameters:
                raise ValueError(
                    f"model {cfg.model!r} does not support pipeline parallelism "
                    f"(no pp_axis in apply); use vit_pp_* or pp=1"
                )
            if cfg.pp_interleave > 1:
                import dataclasses as _dc  # noqa: PLC0415

                m_check = cfg.pp_microbatches or cfg.pp
                if m_check < cfg.pp:
                    raise ValueError(
                        "pp_interleave > 1 requires pp_microbatches >= pp "
                        "(fewer microbatches than stages starves the "
                        "interleaved schedule's warmup ramp)"
                    )
                if not (
                    _dc.is_dataclass(self.model)
                    and hasattr(self.model, "interleave")
                    and hasattr(self.model, "pp_stages")
                ):
                    raise ValueError(
                        f"model {cfg.model!r} does not support the interleaved "
                        f"schedule (no interleave/pp_stages fields); use "
                        f"vit_pp_* or pp_interleave=1"
                    )
                # relay the virtual-stage layout into the model definition
                self.model = _dc.replace(
                    self.model, interleave=cfg.pp_interleave, pp_stages=cfg.pp
                )
            depth = getattr(self.model, "depth", None)
            chunks = cfg.pp * cfg.pp_interleave
            if depth is not None and depth % chunks:
                raise ValueError(
                    f"depth {depth} not divisible by pp*interleave={chunks} chunks"
                )
            if cfg.fused_epoch or cfg.shard_weight_update:
                raise ValueError(
                    "pp > 1 is incompatible with fused_epoch / zero1 "
                    "(grad_clip_norm composes — shard-aware norm in step.py)"
                )
            m = cfg.pp_microbatches or cfg.pp
            per_dev_batch = cfg.batch_size // max(1, self.n_data)
            if per_dev_batch % m:
                raise ValueError(
                    f"per-data-shard batch {per_dev_batch} must divide into "
                    f"{m} microbatches"
                )
            from tpu_dist.parallel.pipeline import bubble_fraction  # noqa: PLC0415

            rank0_print(
                f"pipeline: {cfg.pp} stages x {cfg.pp_interleave} virtual, "
                f"{m} microbatches, bubble fraction "
                f"{bubble_fraction(cfg.pp, m, cfg.pp_interleave):.3f}"
            )
            if cfg.tp > 1:
                if not hasattr(self.model, "pp_tp_param_specs"):
                    raise ValueError(
                        f"model {cfg.model!r} does not support the PP×TP "
                        f"layout (no pp_tp_param_specs); use vit_pp_* or tp=1"
                    )
                self._param_specs = self.model.pp_tp_param_specs(
                    mesh_lib.PIPE_AXIS, mesh_lib.MODEL_AXIS
                )
            else:
                self._param_specs = self.model.pp_param_specs(mesh_lib.PIPE_AXIS)

        # -- data ------------------------------------------------------------
        if cfg.dataset == "synthetic":
            self.train_data = synthetic_cifar(cfg.synthetic_n, cfg.num_classes, seed=1)
            self.test_data = synthetic_cifar(
                max(cfg.synthetic_n // 5, self.n_devices), cfg.num_classes, seed=2
            )
        elif cfg.dataset == "synthetic_learnable":
            from tpu_dist.data.synthetic import synthetic_quadrant  # noqa: PLC0415

            self.train_data = synthetic_quadrant(cfg.synthetic_n, seed=1)
            self.test_data = synthetic_quadrant(
                max(cfg.synthetic_n // 5, self.n_devices), seed=2
            )
        elif cfg.dataset == "synthetic_multifactor":
            from tpu_dist.data.synthetic import synthetic_multifactor  # noqa: PLC0415

            # train labels carry the task's noise; eval labels are clean so
            # val accuracy measures the true function (data/synthetic.py)
            self.train_data = synthetic_multifactor(cfg.synthetic_n, seed=1)
            self.test_data = synthetic_multifactor(
                max(cfg.synthetic_n // 5, self.n_devices), seed=2, label_noise=0.0
            )
        elif cfg.dataset == "synthetic_tokens":
            from tpu_dist.data.synthetic import synthetic_tokens  # noqa: PLC0415

            if not self._token_model:
                raise ValueError(
                    f"dataset 'synthetic_tokens' needs a token model; {cfg.model!r} "
                    "takes images"
                )
            # sequence length and vocabulary are the model's
            m = self.model
            self.train_data = synthetic_tokens(cfg.synthetic_n, m.seq_len, m.vocab_size, seed=1)
            self.test_data = synthetic_tokens(
                max(cfg.synthetic_n // 5, self.n_devices), m.seq_len, m.vocab_size, seed=2
            )
        elif cfg.dataset == "cifar100":
            self.train_data = load_cifar100(cfg.data_dir, train=True)
            self.test_data = load_cifar100(cfg.data_dir, train=False)
        elif cfg.dataset == "cifar10":
            self.train_data = load_cifar10(cfg.data_dir, train=True)
            self.test_data = load_cifar10(cfg.data_dir, train=False)
        else:
            raise ValueError(f"unknown dataset {cfg.dataset!r}")
        _DATASET_CLASSES = {
            "cifar100": 100, "cifar10": 10,
            "synthetic_learnable": 4, "synthetic_multifactor": 16,
        }
        expected = _DATASET_CLASSES.get(cfg.dataset)
        if expected is not None and cfg.num_classes != expected:
            raise ValueError(
                f"dataset {cfg.dataset!r} has {expected} classes but "
                f"num_classes={cfg.num_classes}; pass --num_classes {expected}"
            )

        nproc, pid = mesh_lib.process_count(), mesh_lib.process_index()
        # reference: per-worker batch = global / nprocs (distributed.py:67);
        # here the per-process slice is further split over local chips by
        # the batch sharding, and grad accumulation slices it once more.
        if cfg.batch_size % self.n_data:
            raise ValueError(
                f"batch_size {cfg.batch_size} must divide over {self.n_data} "
                f"data-parallel devices"
            )
        # under ep>1 the batch shards over ALL devices (expert axis carries data)
        per_device = cfg.batch_size // (self.n_devices if cfg.ep > 1 else self.n_data)
        if per_device == 0 or per_device % cfg.grad_accu_steps:
            raise ValueError(
                f"per-device batch {per_device} must divide by grad_accu_steps="
                f"{cfg.grad_accu_steps}"
            )
        self.local_batch = cfg.batch_size // nproc
        seed = cfg.seed if cfg.seed is not None else 0

        self.train_sampler = DistributedSampler(
            len(self.train_data[0]), nproc, pid, shuffle=True, seed=seed,
            drop_last=cfg.drop_last or cfg.grad_accu_steps > 1,
        )
        self.test_sampler = DistributedSampler(
            len(self.test_data[0]), nproc, pid, shuffle=False, seed=seed
        )
        # fused C++ gather+crop+normalize when built; numpy otherwise.
        # Normalization statistics follow the dataset (CIFAR-100 stats are
        # the reference's utils/dataset.py:8,20).
        from tpu_dist.data import native, transforms  # noqa: PLC0415

        if cfg.dataset == "cifar10":
            stats = dict(mean=transforms.CIFAR10_MEAN, std=transforms.CIFAR10_STD)
        else:
            stats = dict(mean=transforms.CIFAR100_MEAN, std=transforms.CIFAR100_STD)

        # EP: the expert axis carries data everywhere outside the MoE, so the
        # TRAIN batch also shards over every device
        train_axes = (
            (mesh_lib.DATA_AXIS, mesh_lib.EXPERT_AXIS) if cfg.ep > 1 else mesh_lib.DATA_AXIS
        )
        divisor = max(1, (self.n_devices if cfg.ep > 1 else self.n_data) // nproc)
        # eval shards over every non-model axis (seq/expert ways hold
        # different examples — no SP/EP structure needed at eval time)
        if cfg.sp > 1:
            eval_axes = (mesh_lib.DATA_AXIS, mesh_lib.SEQ_AXIS)
        elif cfg.ep > 1:
            eval_axes = (mesh_lib.DATA_AXIS, mesh_lib.EXPERT_AXIS)
        else:
            eval_axes = mesh_lib.DATA_AXIS
        eval_ways = self.n_data * (cfg.sp if cfg.sp > 1 else cfg.ep if cfg.ep > 1 else 1)
        eval_divisor = max(1, eval_ways // nproc)
        # integer ids are gathered by index and placed as they are: no crop,
        # no normalisation, no C++ image path
        images = self.train_data[0].dtype == np.uint8
        self.train_loader = DataLoader(
            *self.train_data, self.local_batch, self.train_sampler, self.mesh,
            gather_transform=functools.partial(
                native.gather_augment, train=True, **stats) if images else None,
            seed=seed, prefetch=cfg.num_workers, batch_divisor=divisor,
            shard_axes=train_axes,
        )
        self.test_loader = DataLoader(
            *self.test_data, self.local_batch, self.test_sampler, self.mesh,
            gather_transform=functools.partial(
                native.gather_augment, train=False, **stats) if images else None,
            seed=seed, with_mask=True, prefetch=cfg.num_workers,
            batch_divisor=eval_divisor, shard_axes=eval_axes,
        )

        # -- model / optimizer state ----------------------------------------
        if cfg.optimizer == "adamw":
            if cfg.fused_optimizer:
                raise ValueError(
                    "fused_optimizer is the Pallas fused-SGD kernel; adamw "
                    "uses the plain (XLA-fused) update"
                )
            from tpu_dist.train.optim import AdamW  # noqa: PLC0415

            self.optimizer = AdamW(
                weight_decay=cfg.weight_decay,
                decay_mask=cfg.adamw_decay_mask,
            )
            rank0_print(
                f"=> adamw decay_mask={cfg.adamw_decay_mask} "
                "(auto: rank<=1 leaves excluded from weight decay; "
                "--adamw_decay_mask all restores decay-everything)"
            )
        elif cfg.optimizer == "sgd":
            self.optimizer = SGD(
                momentum=cfg.momentum,
                weight_decay=cfg.weight_decay,
                fused=cfg.fused_optimizer,
            )
        elif cfg.optimizer in ("lars", "lamb"):
            if cfg.fused_optimizer:
                raise ValueError(
                    "fused_optimizer is the Pallas fused-SGD kernel; "
                    f"{cfg.optimizer} uses the plain (XLA-fused) update"
                )
            if cfg.shard_weight_update:
                raise ValueError(
                    f"{cfg.optimizer} needs per-layer norms, which the "
                    "ZeRO-1 flat layout destroys — use --fsdp (leaf-"
                    "grained sharding) for a sharded large-batch run"
                )
            from tpu_dist.train.optim import LAMB, LARS  # noqa: PLC0415

            if cfg.optimizer == "lars":
                self.optimizer = LARS(
                    momentum=cfg.momentum, weight_decay=cfg.weight_decay
                )
            else:
                self.optimizer = LAMB(weight_decay=cfg.weight_decay)
            if cfg.lr_base_batch <= 0 or cfg.warmup_epochs <= 0:
                rank0_print(
                    f"=> WARNING: {cfg.optimizer} without the full "
                    "large-batch recipe (--lr_base_batch for linear LR "
                    "scaling + --warmup_epochs) — trust ratios alone "
                    "rarely save an unscaled schedule"
                )
        else:
            raise ValueError(
                f"unknown optimizer {cfg.optimizer!r} (sgd | adamw | lars | lamb)"
            )
        params, bn_state = self.model.init(jax.random.PRNGKey(seed))
        state = TrainState.create(params, bn_state, self.optimizer)
        if cfg.grad_compression == "int8_ef" and not cfg.fsdp:
            # error-feedback residuals are TrainState: zero-initialized
            # here, quantization error flows into them each step, and they
            # ride every checkpoint save/restore like the momentum buffers
            from tpu_dist.train.step import ef_state_host_zeros  # noqa: PLC0415

            state = state._replace(ef=ef_state_host_zeros(
                params, self.n_data, zero1=cfg.shard_weight_update
            ))
        self._fsdp_opt_specs = None
        if cfg.fsdp:
            from tpu_dist.parallel.fsdp import (  # noqa: PLC0415
                compose_fsdp_specs,
                fsdp_specs,
            )

            if cfg.tp > 1:
                # FSDP × TP: overlay data-axis sharding on the model's
                # Megatron specs; the GSPMD engine runs the PLAIN apply
                # (no tp_axis/psum — the partitioner inserts collectives
                # for both axes from the specs alone)
                self._fsdp_specs = compose_fsdp_specs(
                    params, self.mesh,
                    self.model.tp_param_specs(mesh_lib.MODEL_AXIS),
                )
            else:
                self._fsdp_specs = fsdp_specs(params, self.mesh)
            self._fsdp_opt_specs = self.optimizer.state_specs(self._fsdp_specs)
        if cfg.shard_weight_update and cfg.fused_epoch:
            raise ValueError(
                "shard_weight_update (ZeRO-1) is scoped to the plain DP "
                "step by design — the fused-epoch scan keeps params "
                "replicated; use --fsdp for sharded state"
            )
        if cfg.mid_epoch_save_every and cfg.fused_epoch:
            raise ValueError(
                "mid_epoch_save_every needs per-step granularity; "
                "--fused_epoch compiles the whole epoch into one call "
                "(no step boundary to snapshot at)"
            )
        if cfg.device_metrics:
            # same wall as make_train_step, caught at the config layer,
            # plus the two engine exclusions only the trainer knows about
            if (
                cfg.fsdp or cfg.shard_weight_update
                or cfg.tp > 1 or cfg.ep > 1 or cfg.pp > 1
            ):
                raise ValueError(
                    "--device_metrics is scoped to the replicated-param "
                    "paths (plain DP/SP, any --grad_compression): under "
                    "ZeRO-1/FSDP/TP/EP/PP the reduced gradient exists "
                    "only as shards, and the global norms would need the "
                    "extra collectives the TD107 zero-cost contract "
                    "forbids (docs/observability.md)"
                )
            if cfg.fused_epoch:
                raise ValueError(
                    "--device_metrics needs the per-step metrics fetch; "
                    "--fused_epoch compiles the epoch into one call with "
                    "epoch-mean metrics, so the per-step norms would be "
                    "averaged away (refusing to silently ignore the flag)"
                )
        if cfg.anomaly_action not in ("off", "warn", "snapshot"):
            raise ValueError(
                f"anomaly_action must be off|warn|snapshot, got "
                f"{cfg.anomaly_action!r}"
            )
        if cfg.anomaly_action == "snapshot" and not cfg.ckpt_dir:
            raise ValueError(
                "--anomaly_action snapshot writes an emergency mid-epoch "
                "checkpoint and needs --ckpt_dir (refusing to silently "
                "degrade to 'warn')"
            )
        self._anomaly = None
        if cfg.anomaly_action != "off":
            from tpu_dist.obs.anomaly import AnomalyDetector  # noqa: PLC0415

            # raises on a degenerate window before training starts
            self._anomaly = AnomalyDetector(
                window=cfg.anomaly_window,
                loss_spike=cfg.anomaly_loss_spike,
                grad_spike=cfg.anomaly_grad_spike,
            )
        # place on the mesh (DDP's init-time param broadcast; sharded
        # placements for TP params / ZeRO-1 optimizer state)
        self.state = self._place_state(state)
        # auto-recovery LR backoff: deterministic data order means a bare
        # retry of a diverged epoch would diverge identically — each
        # recovery scales the schedule down (cfg.recover_lr_factor)
        self._lr_scale = 1.0
        self._state_poisoned = False
        self._best_top1 = -1.0
        base_lr = cfg.lr
        if cfg.lr_base_batch > 0:
            # Goyal linear-scaling rule — the large-batch recipe's first
            # half; the second half is the warmup ramp below
            from tpu_dist.train.optim import linear_scaled_lr  # noqa: PLC0415

            base_lr = linear_scaled_lr(cfg.lr, cfg.lr_base_batch, cfg.batch_size)
            rank0_print(
                f"=> linear LR scaling: {cfg.lr} x {cfg.batch_size}/"
                f"{cfg.lr_base_batch} = {base_lr:g}"
            )
        if cfg.lr_schedule == "cosine":
            self.lr_schedule = cosine_lr(base_lr, cfg.epochs, cfg.warmup_epochs)
        else:
            self.lr_schedule = multistep_lr(
                base_lr, cfg.lr_milestones, cfg.lr_gamma,
                warmup_epochs=cfg.warmup_epochs,
            )

        compute_dtype = jnp.bfloat16 if cfg.bf16 else jnp.float32
        if cfg.fsdp:
            from tpu_dist.parallel.fsdp import (  # noqa: PLC0415
                make_fsdp_eval_step,
                make_fsdp_train_step,
            )

            self.train_step = make_fsdp_train_step(
                self.model.apply, self.optimizer, self.mesh, self._fsdp_specs,
                opt_specs=self._fsdp_opt_specs,
                grad_accum_steps=cfg.grad_accu_steps,
                compute_dtype=compute_dtype,
                label_smoothing=cfg.label_smoothing,
                grad_clip_norm=cfg.grad_clip_norm,
                moe_aux_coef=cfg.moe_aux_coef,
                remat=cfg.remat,
                model_kwargs=self._attn_model_kwargs() or None,
            )
            self.eval_step = make_fsdp_eval_step(
                self.model.apply, self.mesh, self._fsdp_specs,
                opt_specs=self._fsdp_opt_specs,
                compute_dtype=compute_dtype,
                model_kwargs=self._attn_model_kwargs() or None,
            )
        else:
            from tpu_dist.train.step import ef_state_spec  # noqa: PLC0415

            self.train_step = self._build_train_step(cfg, compute_dtype)
            self.eval_step = make_eval_step(
                self.model.apply, self.mesh, compute_dtype=compute_dtype,
                model_kwargs=self._attn_model_kwargs() or None,
                model_loss=getattr(self.model, "loss", None),
                axis=eval_axes,
                tp_axis=mesh_lib.MODEL_AXIS if cfg.tp > 1 else None,
                ep_axis=mesh_lib.EXPERT_AXIS if cfg.ep > 1 else None,
                pp_axis=mesh_lib.PIPE_AXIS if cfg.pp > 1 else None,
                param_specs=self._param_specs,
                opt_specs=(
                    self.optimizer.state_specs(self._param_specs)
                    if self._param_specs is not None
                    else None
                ),
                ef_specs=ef_state_spec(
                    cfg.grad_compression, zero1=cfg.shard_weight_update
                ),
            )

        self._fused_runner = None
        if cfg.fused_epoch:
            from tpu_dist.train.epoch import (  # noqa: PLC0415
                make_fused_epoch,
                make_fused_eval,
                put_dataset_on_device,
            )

            self._fused_data = put_dataset_on_device(self.mesh, *self.train_data)
            self._fused_runner = make_fused_epoch(
                self.model.apply, self.optimizer, self.mesh,
                batch_per_device=cfg.batch_size // self.n_devices,
                sync_bn=cfg.sync_bn, compute_dtype=compute_dtype,
                moe_aux_coef=cfg.moe_aux_coef,
                grad_compression=cfg.grad_compression,
                model_kwargs=self._attn_model_kwargs() or None, **stats,
            )
            # round the test set UP to a device multiple with label=-1
            # padding so fused eval counts every real example exactly once
            ti, tl = self.test_data
            pad = (-len(tl)) % self.n_devices
            if pad:
                ti = np.concatenate([ti, np.zeros((pad,) + ti.shape[1:], ti.dtype)])
                tl = np.concatenate([tl, np.full(pad, -1, tl.dtype)])
            self._fused_test_data = put_dataset_on_device(self.mesh, ti, tl)
            from tpu_dist.train.step import ef_state_spec  # noqa: PLC0415

            self._fused_eval = make_fused_eval(
                self.model.apply, self.mesh,
                batch_per_device=cfg.batch_size // self.n_devices,
                compute_dtype=compute_dtype,
                ef_specs=ef_state_spec(cfg.grad_compression),
                model_kwargs=self._attn_model_kwargs() or None, **stats,
            )

        self._async_ckpt = None  # created lazily by _ckpt_io()
        self._heartbeat = None  # created by fit() (rank 0, --heartbeat_file)
        self._flight = None  # per-rank flight recorder, armed by fit()
        #                      (--crash_dir; obs/flight.py)
        self._fault_handle = None  # armed faulthandler (stack capture)
        self._exporter = None  # live OpenMetrics publisher, created by fit()
        self._alerts = None  # AlertEngine, created by fit() per run
        self._export_rollup = {}  # latest epoch/health scalars for export
        self._export_t = float("-inf")  # exposition throttle mark
        self._trace_events = []  # drained spans held for --trace_file export
        self._step_traced = False  # first dispatch of THIS Trainer compiles
        self._history = None  # live MetricsHistory while fit() runs — the
        #                       step loop's device_stats/anomaly records
        self._tb = None  # SummaryWriter while fit() runs (--tensorboard_dir)
        # XLA cost/memory accounting of the train step, captured ONCE at
        # first dispatch (obs/costmodel.py): {} = capture failed, don't retry
        self._step_cost = None
        # executable-cache watcher: counts compiles, flags mid-run retraces
        self._compile_watch = costmodel_lib.CompileWatcher(self.train_step)
        # -- HBM pre-flight (obs/memory.py, docs/observability.md "HBM
        # ledger & OOM forensics"): static per-leaf accounting of the
        # state (params/opt-state/EF/BN at their SHARDED extents — a
        # ZeRO-1 flat momentum counts ceil(L/n) per chip) plus one
        # per-device input shard, priced against the per-chip HBM budget
        # BEFORE the first compile can OOM — the lint ROADMAP item 3
        # names. Pure shape/sharding metadata arithmetic; TD115 pins
        # that arming it leaves the traced step byte-identical.
        from tpu_dist.obs import memory as memory_lib  # noqa: PLC0415

        batch_sds = None
        try:
            img, lbl = self.train_data
            per_dev = max(cfg.batch_size // self.n_devices, 1)
            batch_sds = {
                "images": jax.ShapeDtypeStruct(
                    (per_dev,) + tuple(img.shape[1:]), img.dtype
                ),
                "labels": jax.ShapeDtypeStruct(
                    (per_dev,) + tuple(lbl.shape[1:]), lbl.dtype
                ),
            }
        except Exception:  # tpu-dist: ignore[TD006] — an exotic dataset
            pass  # shape only costs the batch row, never the pre-flight
        self._mem_static = memory_lib.static_ledger(
            params=self.state.params, opt_state=self.state.opt_state,
            ef=self.state.ef, bn_state=self.state.bn_state,
            batch=batch_sds,
        )
        counters_lib.set_gauge(
            "mem.static_bytes_per_device",
            self._mem_static["bytes_per_device"],
        )
        self._mem_record = None  # the first-dispatch ledger snapshot
        self._mem_feasibility = memory_lib.preflight_check(
            self._mem_static["bytes_per_device"],
            budget_bytes=cfg.hbm_budget_bytes,
            headroom=cfg.memory_headroom,
            action=cfg.memory_check,
        )  # InfeasibleMemoryError under --memory_check refuse
        if self._mem_feasibility and not self._mem_feasibility["fits"]:
            rank0_print(
                "WARNING: static HBM requirement "
                f"{memory_lib.fmt_bytes(self._mem_feasibility['required_bytes'])}"
                "/device exceeds "
                f"{cfg.memory_headroom:.0%} of the "
                f"{memory_lib.fmt_bytes(self._mem_feasibility['budget_bytes'])}"
                " per-chip budget — expect RESOURCE_EXHAUSTED; shard more "
                "or shrink the batch (--memory_check refuse stops here)"
            )
        # run identity: config hash + construction second, stamped ONCE per
        # Trainer (docs/observability.md) — every history record of this
        # run carries the same id, repeated fit() calls included, and a
        # resume (new process, same config) gets a fresh one
        import dataclasses as _dc  # noqa: PLC0415
        import hashlib  # noqa: PLC0415
        import json as _json  # noqa: PLC0415

        cfg_hash = hashlib.sha1(
            _json.dumps(_dc.asdict(cfg), sort_keys=True, default=str).encode()
        ).hexdigest()[:8]
        self._run_id = f"{cfg_hash}-{int(time.time())}"
        # arm host-span tracing on the primary BEFORE the resume-path
        # restore below, so the restore ladder's ckpt/restore spans land in
        # the trace (fit() re-arms with fresh=False, keeping them). The
        # monotonic stamp here is the run's single clock origin: the span
        # recorder zeroes on it now, and fit() hands it to MetricsHistory
        # as the rel_s origin — exported epoch bars and spans line up, and
        # a second fit() on this instance continues the same timeline.
        self._telemetry = bool(
            mesh_lib.is_primary() and (cfg.log_file or cfg.trace_file)
        )
        self._telemetry_t0 = time.monotonic()
        if self._telemetry:
            spans_lib.enable()
        self.start_epoch = 0
        self._resume_step = 0  # >0 only after restoring a mid-epoch snapshot
        self._resume_examples = 0  # >0 only on an ELASTIC mid-epoch resume
        #                            (consumed-prefix offset; sampler.set_offset)
        # the snapshot's final-step metrics: replayed when a resumed epoch
        # has zero steps left (the interrupt landed after the epoch's last
        # step), so the epoch record still matches the uninterrupted run
        self._resume_metrics = None
        self._step_metrics = None  # (epoch, steps_done, device metrics)
        self._epoch_start_examples = 0  # the running epoch's entry offset
        # logical param length L — the world-size-independent coordinate
        # every elastic flat layout (ZeRO-1 opt vectors, EF residuals) is
        # padded from; stamped into every checkpoint's elastic meta
        from tpu_dist.elastic.remap import params_len  # noqa: PLC0415

        self._params_len = params_len(self.state.params)
        self._last_reshard_s = 0.0  # wall time of the last elastic remap
        self._elastic_resume = None  # 'resume' history record, logged by fit
        # atomic training position for _emergency_save: (state, epoch,
        # steps_done, epoch_complete). Fresh start = complete through
        # epoch -1 (nothing to snapshot); _restore_latest re-publishes.
        self._progress = (self.state, -1, 0, True)
        if cfg.resume and cfg.ckpt_dir:
            # template = current state (matches sharded layouts too);
            # raises on a format-mismatched ckpt_dir (_restore_latest).
            # Goodput: the plain restore is ckpt time, but an ELASTIC
            # reshard (restore onto a new dp extent) is recovery time —
            # the ledger's recovery_s bucket carries reshard+relaunch cost
            t_res = time.monotonic()
            epoch = self._restore_latest()
            restore_s = time.monotonic() - t_res
            self._goodput.add(
                "ckpt", max(restore_s - self._last_reshard_s, 0.0)
            )
            self._goodput.add("recovery", self._last_reshard_s)
            if epoch is not None:
                # a mid-epoch snapshot re-enters its own epoch at the saved
                # step (or, elastically, at the consumed-example offset); a
                # clean end-of-epoch ckpt starts the next epoch
                self.start_epoch = (
                    epoch if (self._resume_step or self._resume_examples)
                    else epoch + 1
                )
                self._seed_global_step()

    def _seed_global_step(self) -> None:
        """Re-anchor the ``--profile_steps`` grid after a restore. The
        grid is RUN-global (the flag's contract: 'global steps'), so a
        resumed process must not restart it at 0 — a manual window that
        already ran before the preemption would re-fire aimed at the
        wrong steps. Per-epoch step count is the loader length capped by
        ``--steps_per_epoch``, the same bound ``train_epoch`` honors; a
        window cut short by the preemption resumes mid-range (the
        profiler captures the remaining overlap)."""
        n = len(self.train_loader)
        if self.cfg.steps_per_epoch is not None:
            n = min(n, self.cfg.steps_per_epoch)
        self._global_step = self.start_epoch * n + self._resume_step

    def _ckpt_io(self):
        """Sync module functions, the sharded writer (``--sharded_ckpt``),
        or an async writer (``--async_ckpt``: plain, or snapshot-then-write
        sharded when combined with ``--sharded_ckpt``); the async writers
        are created lazily so each ``fit()`` gets a fresh pool after
        ``_ckpt_close()`` released the previous worker thread."""
        if not self.cfg.async_ckpt:
            if self.cfg.sharded_ckpt:
                # stateless (staticmethods) — hand back the class, same as
                # the emergency-save path uses it
                return ckpt_lib.ShardedCheckpointer
            return ckpt_lib
        if self._async_ckpt is None:
            self._async_ckpt = (
                ckpt_lib.AsyncShardedCheckpointer()
                if self.cfg.sharded_ckpt
                else ckpt_lib.AsyncCheckpointer()
            )
        return self._async_ckpt

    def _ckpt_close(self, suppress: bool = False) -> None:
        """Bounded drain + release of the async writer
        (``--ckpt_drain_timeout_s``; ≤0 waits forever). ``suppress=True``
        logs a writer error instead of raising — for paths where an
        exception is already propagating (interrupt/divergence) and must
        not be masked. A drain that times out with writes still in flight
        is a COUNTED, loud data loss (``ckpt.drain_abandoned``) — never a
        silent one: the newest data on disk is then the last published
        (plain) / committed (sharded) checkpoint."""
        if self._async_ckpt is None:
            return
        writer, self._async_ckpt = self._async_ckpt, None
        timeout = self.cfg.ckpt_drain_timeout_s
        timeout = timeout if timeout and timeout > 0 else None
        try:
            drained = writer.close(timeout=timeout)
        except Exception as e:
            if not suppress:
                raise
            rank0_print(f"WARNING: background checkpoint write failed: {e}")
            return
        if not drained:
            n = writer.in_flight
            counters_lib.inc("ckpt.drain_abandoned", n)
            rank0_print(
                f"WARNING: abandoned {n} in-flight background checkpoint "
                f"write(s) after the {timeout:.0f}s drain timeout "
                "(--ckpt_drain_timeout_s) — their snapshots are LOST; the "
                "newest checkpoint on disk is the last one committed"
            )
            if not suppress:
                raise RuntimeError(
                    f"background checkpoint drain timed out with {n} "
                    "write(s) in flight (see the warning above)"
                )

    def _build_train_step(self, cfg: TrainConfig, compute_dtype):
        mk = {}
        if cfg.pp > 1 and cfg.pp_microbatches:
            mk["n_microbatches"] = cfg.pp_microbatches
        if cfg.sp > 1 and cfg.sp_mode != "ring":
            mk["sp_mode"] = cfg.sp_mode
        mk.update(self._attn_model_kwargs())
        return make_train_step(
            self.model.apply, self.optimizer, self.mesh,
            grad_accum_steps=cfg.grad_accu_steps,
            sync_bn=cfg.sync_bn,
            compute_dtype=compute_dtype,
            shard_weight_update=cfg.shard_weight_update,
            label_smoothing=cfg.label_smoothing,
            grad_clip_norm=cfg.grad_clip_norm,
            moe_aux_coef=cfg.moe_aux_coef,
            seq_axis=mesh_lib.SEQ_AXIS if cfg.sp > 1 else None,
            tp_axis=mesh_lib.MODEL_AXIS if cfg.tp > 1 else None,
            ep_axis=mesh_lib.EXPERT_AXIS if cfg.ep > 1 else None,
            pp_axis=mesh_lib.PIPE_AXIS if cfg.pp > 1 else None,
            param_specs=self._param_specs,
            remat=cfg.remat,
            grad_compression=cfg.grad_compression,
            device_metrics=cfg.device_metrics,
            model_kwargs=mk or None,
            model_loss=getattr(self.model, "loss", None),
        )

    def _attn_model_kwargs(self) -> dict:
        """Snapshot the attention implementation into the step closure at
        BUILD time. The process-global default (``set_default_attention_impl``)
        is only a fallback read at trace time — a second Trainer constructed
        before this one's step traces must not flip this one's attention
        (ADVICE r2)."""
        import inspect  # noqa: PLC0415

        if "attn_impl" in inspect.signature(self.model.apply).parameters:
            return {"attn_impl": self._attn_impl(self.cfg)}
        return {}

    @staticmethod
    def _attn_impl(cfg: TrainConfig) -> str:
        """``--flash_attention`` forces the tiled kernel; otherwise the
        attention chooses by shape ("auto": the whole-sequence kernel where
        it fits, XLA elsewhere). FSDP pins XLA: its step is one
        GSPMD-partitioned jit (no shard_map), where a Pallas call has no
        partitioning rule."""
        if cfg.flash_attention:
            return "flash"
        return "xla" if cfg.fsdp else "auto"

    def _ckpt_meta(self) -> dict:
        """Layout tag written with every checkpoint. Interleaved pipeline
        storage permutes block order on disk (vit_pp device-major layout), so
        a ckpt is only loadable under the SAME pp/pp_interleave — the tag
        lets resume refuse a mismatch instead of silently training with
        permuted blocks. AdamW additionally stamps its decay mask (ADVICE
        r3): the opt-state SHAPES are mask-independent, so a resume under a
        different mask would succeed and silently change the update math."""
        cfg = self.cfg
        meta = {"pp": cfg.pp, "pp_interleave": cfg.pp_interleave}
        if cfg.optimizer == "adamw":
            meta["adamw_decay_mask"] = cfg.adamw_decay_mask
        if self._lr_scale != 1.0:
            # auto-recovery backoff survives preemption: a --resume that
            # replayed the UNSCALED schedule would re-diverge identically
            meta["lr_scale"] = self._lr_scale
        # mesh-shape portability stamp (docs/resilience.md "Elastic
        # training"): the dp extent the state is laid out for, the process
        # count (the sampler's shard count), and the logical param length
        # — what a restore onto a DIFFERENT world size needs to remap the
        # ZeRO-1/EF flat layouts deterministically
        from tpu_dist.elastic.remap import elastic_stamp  # noqa: PLC0415

        meta["elastic"] = elastic_stamp(
            self.n_data, mesh_lib.process_count(), self._params_len
        )
        return meta

    def _mid_epoch_position(self, steps_done: int) -> dict:
        """The data-position stamps of a mid-epoch snapshot. The legacy
        triple (step, GLOBAL batch size, seed) pins the position exactly
        for a same-world resume; ``mid_epoch_examples`` (the consumed
        prefix of the epoch permutation — entry offset plus steps since)
        and ``mid_epoch_procs`` make it world-portable: a resume at a
        different process count re-partitions ``order[examples:]`` over
        the new shards so nothing is dropped or double-seen."""
        cfg = self.cfg
        # clamp to the dataset size: the final batch of a drop_last=False
        # epoch is wrap-around padded, so step*batch can overshoot N — an
        # unclamped stamp would make the elastic resume's set_offset raise
        # at exactly the moment the feature exists for (offset == N means
        # "nothing left of this epoch", which is the truth)
        consumed = min(
            self._epoch_start_examples + steps_done * cfg.batch_size,
            len(self.train_data[0]),
        )
        out = {
            "mid_epoch_step": int(steps_done),
            "mid_epoch_batch_size": cfg.batch_size,
            "mid_epoch_seed": cfg.seed or 0,
            "mid_epoch_procs": mesh_lib.process_count(),
            "mid_epoch_examples": int(consumed),
        }
        # carry the final dispatched step's metrics when they describe
        # exactly this position: an interrupt that lands after an epoch's
        # LAST step resumes with nothing left to run, and without this
        # stamp the epoch record (loss above all) would silently vanish
        stamped = self._step_metrics
        prog = self._progress
        if (
            stamped is not None
            and stamped[0] == prog[1]
            and stamped[1] == int(steps_done)
        ):
            try:
                out["mid_epoch_metrics"] = _fetch_metrics(stamped[2])
            except RuntimeError:  # tpu-dist: ignore[TD006] — best-effort
                # garnish on the emergency snapshot: a donated/deleted
                # device buffer must never block the save itself (the
                # record then degrades to the pre-fix lossless-but-
                # lossy-logging behavior instead of dying mid-SIGTERM)
                pass
        return out

    def _check_ckpt_layout(self, path: str) -> None:
        self._check_ckpt_meta(ckpt_lib.read_meta(path), path)

    def _check_ckpt_meta(self, meta: dict, path: str) -> None:
        """Config-mismatch stamp checks. Everything here raises the typed
        :class:`ConfigMismatchError` — OPERATOR errors a restore must not
        fall past. A world-size change deliberately does NOT land here: it
        surfaces as :class:`ElasticShapeMismatch` from the checkpoint
        layer and is handled by the elastic remapper (docs/resilience.md
        "Elastic training"), so shrinking the pod no longer pattern-
        matches to config drift."""
        from tpu_dist.elastic.errors import ConfigMismatchError  # noqa: PLC0415

        cfg = self.cfg
        ck_v = meta.get("pp_interleave")
        ck_pp = meta.get("pp")
        if ck_v is None:
            # pre-layout-tag checkpoint: blocks are in logical depth order —
            # loadable only by non-interleaved configs
            if cfg.pp_interleave > 1:
                raise ConfigMismatchError(
                    f"checkpoint {path} has no pipeline-layout tag (written "
                    f"before interleaving existed, logical block order) — it "
                    f"cannot be resumed with pp_interleave={cfg.pp_interleave}"
                )
            return
        if ck_v != cfg.pp_interleave or (
            (ck_v > 1 or cfg.pp_interleave > 1) and ck_pp != cfg.pp
        ):
            raise ConfigMismatchError(
                f"checkpoint {path} was written with pp={ck_pp}, "
                f"pp_interleave={ck_v} — its block storage order is "
                f"layout-specific; resume with the same flags (got "
                f"pp={cfg.pp}, pp_interleave={cfg.pp_interleave})"
            )
        if cfg.optimizer == "adamw":
            ck_mask = meta.get("adamw_decay_mask")
            if ck_mask is None:
                # pre-stamp AdamW checkpoint: can't know which mask trained
                # it — warn rather than block (resume stays possible, but
                # the operator is told the math may shift)
                rank0_print(
                    f"WARNING: checkpoint {path} predates the "
                    "adamw_decay_mask stamp; resuming with "
                    f"--adamw_decay_mask {cfg.adamw_decay_mask} — if the "
                    "run was trained with a different mask, weight decay "
                    "on bias/norm leaves silently changes from here on"
                )
            elif ck_mask != cfg.adamw_decay_mask:
                raise ConfigMismatchError(
                    f"checkpoint {path} was trained with adamw_decay_mask="
                    f"{ck_mask!r} but this run uses "
                    f"{cfg.adamw_decay_mask!r} — the opt-state shapes are "
                    "identical, so resuming would silently change which "
                    "leaves get weight decay mid-training; pass "
                    f"--adamw_decay_mask {ck_mask} to resume faithfully"
                )

    def _check_mesh_host_layout(self) -> None:
        """Refuse multi-host meshes whose model axes cross hosts: TP/EP/PP
        collectives must ride ICI, not DCN (SURVEY §2.2 N1; device_mesh
        builds host-major so any group dividing the local device count is
        intra-host — this catches the layouts where it can't be)."""
        if jax.process_count() <= 1:
            return
        cfg = self.cfg
        hard = [
            a for a, w in (
                (mesh_lib.MODEL_AXIS, cfg.tp),
                (mesh_lib.EXPERT_AXIS, cfg.ep),
                (mesh_lib.PIPE_AXIS, cfg.pp),
            )
            if w > 1 and a in self.mesh.axis_names
        ]
        if hard and not mesh_lib.model_axes_intra_host(self.mesh, hard):
            raise ValueError(
                f"mesh lays model axes {hard} across hosts (DCN): with "
                f"{jax.local_device_count()} devices/host, keep "
                f"tp*ep*pp ways a divisor of the local device count"
            )
        if (
            cfg.sp > 1
            and mesh_lib.SEQ_AXIS in self.mesh.axis_names
            and not mesh_lib.model_axes_intra_host(self.mesh, [mesh_lib.SEQ_AXIS])
        ):
            # ring attention still works over DCN, just slower — warn only
            rank0_print(
                "WARNING: sequence-parallel axis spans hosts; ring attention "
                "will run over DCN instead of ICI"
            )

    def _place_state(self, state: TrainState) -> TrainState:
        """Mesh placement for every supported layout: replicated (default),
        per-leaf TP shardings, ZeRO-1 flat-sharded optimizer state, and the
        data-axis-sharded int8_ef residuals (placed apart from the
        replicated bulk — they are per-replica by construction)."""
        cfg = self.cfg
        ef = state.ef
        if ef:
            from tpu_dist.train.step import ef_state_spec  # noqa: PLC0415

            ef = mesh_lib.place_host_tree(
                self.mesh, ef,
                ef_state_spec(
                    cfg.grad_compression, zero1=cfg.shard_weight_update
                ),
            )
            state = state._replace(ef=())
            return self._place_state_bulk(state)._replace(ef=ef)
        return self._place_state_bulk(state)

    def _place_state_bulk(self, state: TrainState) -> TrainState:
        cfg = self.cfg
        rep = mesh_lib.replicated(self.mesh)
        if self._fsdp_specs is not None:  # FSDP: params+momentum data-sharded
            return TrainState(
                params=mesh_lib.place_host_tree(
                    self.mesh, state.params, self._fsdp_specs
                ),
                bn_state=mesh_lib.place_host_tree(self.mesh, state.bn_state),
                opt_state=mesh_lib.place_host_tree(
                    self.mesh, state.opt_state, self._fsdp_opt_specs
                ),
                step=mesh_lib.place_host_tree(self.mesh, state.step),
            )
        if self._param_specs is not None:  # TP/EP/PP per-leaf shardings
            # place_host_tree also covers the multi-host case, where
            # device_put cannot target non-addressable model shards.
            # Optimizer state may not mirror the param tree (AdamW) —
            # its layout comes from the optimizer.
            return TrainState(
                params=mesh_lib.place_host_tree(
                    self.mesh, state.params, self._param_specs
                ),
                bn_state=mesh_lib.place_host_tree(self.mesh, state.bn_state),
                opt_state=mesh_lib.place_host_tree(
                    self.mesh, state.opt_state,
                    self.optimizer.state_specs(self._param_specs),
                ),
                step=mesh_lib.place_host_tree(self.mesh, state.step),
            )
        if cfg.shard_weight_update:
            # replace the per-leaf init tree BEFORE replication — device_put
            # of the full mu/nu (2× params in f32) to every chip just to
            # discard it for the flat template would spike init HBM on
            # exactly the models ZeRO-1 exists for
            opt_np = state.opt_state
            placed = jax.device_put(state._replace(opt_state=()), rep)
            from tpu_dist.train.step import init_sharded_opt_state  # noqa: PLC0415

            tmpl = init_sharded_opt_state(
                state.params, self.mesh, optimizer=self.optimizer
            )
            # fresh init (per-leaf tree layout) vs a restored flat state:
            # restored matches the template's structure AND leaf shapes
            # (SGD: one 1-D vector; AdamW: {mu, nu} vectors + count scalar)
            t_leaves, t_def = jax.tree_util.tree_flatten(tmpl)
            o_leaves, o_def = jax.tree_util.tree_flatten(opt_np)
            if t_def == o_def and all(
                getattr(o, "shape", None) == t.shape
                for o, t in zip(o_leaves, t_leaves)
            ):
                # restored flat state: re-place each buffer with the
                # template's shard layout (a wrong-width checkpoint never
                # reaches here — the ckpt layer's shape validation raises
                # first)
                opt = jax.tree_util.tree_map(
                    lambda o, t: jax.device_put(np.asarray(o), t.sharding),
                    opt_np, tmpl,
                )
            else:
                opt = tmpl  # fresh init (per-leaf tree layout) → flat zeros
            return placed._replace(opt_state=opt)
        return jax.device_put(state, rep)

    # -- loops ---------------------------------------------------------------

    def train_epoch(
        self, epoch: int, start_step: int = 0, start_examples: int = 0
    ) -> dict:
        if self._fused_runner is not None:
            if start_step or start_examples:
                raise ValueError(
                    "mid-epoch resume (checkpoint carries mid_epoch_step="
                    f"{start_step or start_examples}) is not possible with "
                    "--fused_epoch: the whole epoch is one compiled call; "
                    "resume without --fused_epoch to continue from the "
                    "exact batch"
                )
            return self._train_epoch_fused(epoch)
        cfg = self.cfg
        # Epoch-boundary clocks (docs/observability.md): head = entry -> the
        # first next() of the loader, refill = that first wait, drain = the
        # block_until_ready, tail = drain -> return. One perf_counter read
        # per boundary feeds the always-on train.epoch_*_s counter and,
        # recorder on, the span (spans.add_timed).
        t_epoch = time.perf_counter()
        self.train_sampler.set_epoch(epoch)  # shuffle correctness (tutorials/2:§2)
        if start_examples:
            # elastic mid-epoch re-entry: skip the old world's consumed
            # prefix of the epoch permutation and re-partition the
            # remainder over THIS world's shards (exactness argument in
            # sampler.set_offset; set_epoch above cleared any prior offset
            # so only the resumed epoch is shortened)
            self.train_sampler.set_offset(start_examples)
        self._epoch_start_examples = start_examples
        lr = self._lr(epoch)
        losses = AverageMeter("Loss", ":.4e")  # epoch-avg of the logged steps
        images_seen = 0
        t0 = time.time()
        nb = len(self.train_loader)
        metrics = {}
        # Step-phase split on EXISTING sync points only (docs/observability
        # .md): data-wait = blocking in the loader iterator, dispatch = the
        # train_step call (async enqueue; step 0 also holds the compile),
        # host-fetch = the metric device_get the loop already does. No new
        # device_get/block_until_ready enters the hot loop — TD106 and the
        # fetch-count test pin that.
        timer = StepTimer(warmup_steps=1)  # lap 0 would be the compile step
        phase = {"data": 0.0, "dispatch": 0.0, "fetch": 0.0}
        hb = self._heartbeat
        steps_run = 0
        # goodput baselines: compile seconds and ckpt time spent DURING
        # this epoch are attributed to their own buckets and subtracted
        # out of the epoch's productive remainder (obs/goodput.py)
        compile_s0 = counters_lib.get("compile.seconds")
        ckpt_s0 = self._goodput.window_value("ckpt")

        def timed_batches(src):
            it = iter(src)
            # the first wait of an epoch is the boundary's refill: the queue
            # is empty and the producer thread starts inside this next()
            t_w = spans_lib.add_timed(
                "train/epoch_head", "train.epoch_head_s", t_epoch, epoch=epoch
            )
            refill, step = "train.epoch_refill_s", start_step
            while True:
                try:
                    item = next(it)
                except StopIteration:
                    return
                t = spans_lib.add_timed(
                    "train/data_wait", refill, t_w, epoch=epoch, step=step
                )
                phase["data"] += t - t_w
                yield item
                refill, step = None, step + 1
                t_w = time.perf_counter()

        # (state, epoch, completed steps, epoch_complete) published as ONE
        # attribute so an interrupt can never observe a half-updated pair —
        # _emergency_save reads ONLY this to decide what to snapshot
        self._progress = (self.state, epoch, start_step, False)
        for step, (images, labels) in enumerate(
            timed_batches(self.train_loader.iter_from(start_step)),
            start=start_step,
        ):
            if cfg.steps_per_epoch is not None and step >= cfg.steps_per_epoch:
                break
            if self._profiler is not None:
                # capture state machine BEFORE dispatch, so a window
                # opened here covers whole steps; host-side bookkeeping
                # only (TD108 pins that the traced step is unchanged)
                ev = self._profiler.on_step(self._global_step)
                if ev is not None:
                    self._note_profile_event(ev, epoch, step)
            self._global_step += 1
            t_d = time.perf_counter()
            new_state, metrics = self.train_step(self.state, images, labels, lr)
            d_d = time.perf_counter() - t_d
            phase["dispatch"] += d_d
            spans_lib.add_event(
                # only THIS Trainer's very first dispatch holds the trace/
                # compile — epoch 2's step 0 is a plain dispatch and must
                # not read as a retrace in the exported timeline
                "train/dispatch" if self._step_traced else "train/compile+dispatch",
                t_d, d_d, epoch=epoch, step=step,
            )
            if not self._step_traced:
                # first dispatch: the executable exists now — capture XLA's
                # cost accounting once (host-side abstract re-trace, no
                # device work) into the flops/bytes gauges + the per-epoch
                # MFU below. new_state, not state: state's buffers were
                # just donated to the step.
                self._capture_step_cost(new_state, images, labels, lr)
            self._step_traced = True
            if self._compile_watch.observe(context=f"epoch {epoch} step {step}"):
                # the executable cache grew after the first trace: a mid-run
                # retrace (shape/dtype drift) — a full XLA compile stall on
                # every host; counter + rank-0 warning live in the watcher
                # itself (the serving engine shares them), surfaced
                # per-epoch by `obs summarize`
                if (
                    self._profiler is not None
                    and "retrace" in self._profile_triggers
                    and mesh_lib.is_primary()
                ):
                    # catch the post-retrace steps on the device timeline
                    self._profiler.arm("retrace")
            self._step_metrics = (epoch, step + 1, metrics)
            self._progress = (new_state, epoch, step + 1, False)
            self.state = new_state
            images_seen += cfg.batch_size
            steps_run += 1
            timer.tick()
            if hb is not None:
                hb.beat(epoch=epoch, step=step)
            if self._flight is not None:
                # step-boundary slot (one atomic pwrite + counter delta):
                # the ring of a SIGKILLed rank ends exactly at the last
                # completed step — readable after the hardest of kills
                self._flight.step(epoch, step)
            if self._exporter is not None:
                # live exposition at the SAME step-grain throttle as the
                # heartbeat: inside the window only the in-memory HTTP
                # snapshot is (not even) refreshed — the throttle check is
                # the whole per-step cost
                self._export_live()
            if faults.active() is not None:  # zero-cost when no --fault_plan
                self._apply_step_faults(epoch, step, lr)
            want_save = (
                cfg.mid_epoch_save_every
                and cfg.ckpt_dir
                and (step + 1) % cfg.mid_epoch_save_every == 0
            )
            want_log = step % cfg.log_every == 0
            # ONE device fetch serves the snapshot's NaN guard AND the log
            # line — neither issues its own per-key sync
            t_f = time.perf_counter()
            m = _fetch_metrics(metrics) if (want_save or want_log) else None
            if m is not None:
                phase["fetch"] += spans_lib.add_timed(
                    "train/host_fetch", None, t_f, epoch=epoch, step=step
                ) - t_f
                # health layer rides the SAME host copy: device_stats
                # record, anomaly detection (incl. the nonfinite finding,
                # logged BEFORE the NaN guard below raises), per-step
                # TensorBoard scalars — no additional device traffic
                self._observe_health(epoch, step, nb, m)
                # a model with its own loss counts what it returned beside it
                # (nn/nemotron_h.py), from the copy fetched above
                model_note = self.model.count_stats(m) if hasattr(self.model, "count_stats") else ""
            if want_save:
                # periodic EXACT snapshot (kill-9 safety for long epochs):
                # same stamp as the interrupt path — ckpt_{epoch} carries
                # the step offset until the clean end-of-epoch save
                # overwrites it. Rides the async writer when configured.
                # NaN guard FIRST: every other save path refuses to publish
                # a poisoned state, and this one must too (the log_every
                # guard below may not have run since divergence).
                if cfg.nan_guard and not np.isfinite(m["loss"]):
                    raise TrainingDivergedError(
                        f"non-finite loss {m['loss']} at epoch "
                        f"{epoch} step {step} (lr={lr}) — caught at the "
                        f"mid-epoch snapshot boundary before writing it; "
                        f"restore from ckpt_dir to recover"
                    )
                with self._goodput.timed("ckpt"):
                    self._ckpt_io().save(
                        cfg.ckpt_dir, new_state, epoch, cfg.keep_last_ckpts,
                        extra_meta={**self._ckpt_meta(),
                                    **self._mid_epoch_position(step + 1)},
                    )
            if want_log:
                if cfg.nan_guard and not np.isfinite(m["loss"]):
                    raise TrainingDivergedError(
                        f"non-finite loss {m['loss']} at epoch {epoch} step {step} "
                        f"(lr={lr}); restore from ckpt_dir to recover"
                    )
                losses.update(m["loss"], cfg.batch_size)
                # reference per-step line (distributed.py:104-111), plus
                # the health norms when --device_metrics computed them
                rank0_print(
                    f"Epoch:[{epoch}/{cfg.epochs}] step:[{step}/{nb}] "
                    f"lr={lr:.5f} loss={m['loss']:.4f} "
                    f"acc1={m['acc1']:.2f} acc5={m['acc5']:.2f}"
                    + (
                        f" gnorm={m['grad_norm']:.3e} "
                        f"upd={m['update_ratio']:.2e}"
                        if "grad_norm" in m else ""
                    )
                    + model_note
                )
            if preemption.requested():
                # cooperative SIGTERM: the in-flight step is finished and
                # published in _progress — fit() runs the emergency-save
                # discipline on the way out (docs/resilience.md)
                raise PreemptedError(
                    f"SIGTERM observed at epoch {epoch} after step {step} "
                    f"— shutting down at the step boundary"
                )
        t_drain = time.perf_counter()
        jax.block_until_ready(self.state.params)
        t_tail = spans_lib.add_timed(
            "train/epoch_drain", "train.epoch_drain_s", t_drain, epoch=epoch
        )
        # end-of-epoch guard: catches divergence between logged steps BEFORE
        # fit() writes a checkpoint of the poisoned state. One fetch, reused
        # for the returned epoch metrics below.
        if metrics:
            out = _fetch_metrics(metrics)
        elif steps_run == 0 and (start_step or start_examples):
            # the interrupt landed after this epoch's LAST step: nothing
            # was left to run, so replay the snapshot's stamped final-step
            # metrics — the epoch record (loss above all) must match the
            # uninterrupted run, not vanish
            out = dict(self._resume_metrics or {})
        else:
            out = {}
        if cfg.nan_guard and out and not np.isfinite(out["loss"]):
            raise TrainingDivergedError(
                f"non-finite loss {out['loss']} at end of epoch {epoch} "
                f"(lr={lr}); restore from ckpt_dir to recover"
            )
        if cfg.debug_replica_check:
            from tpu_dist.metrics.consistency import check_replicated  # noqa: PLC0415

            check_replicated(self.state.params, "params")
            check_replicated(self.state.bn_state, "bn_state")
        dt = time.time() - t0
        ips = images_seen / dt if dt > 0 else 0.0
        # reference epoch wall-time print (distributed.py:113-115)
        unit = "samples/s" if self._token_model else "img/s"
        rank0_print(
            f"Epoch {epoch} done in {dt:.2f}s ({ips:.{2 if ips < 100 else 0}f} {unit}, "
            f"avg loss {losses.avg:.4f})"
        )
        out.update(epoch_time=dt, images_per_sec=ips)
        # step-phase summary: tail latency + where the wall time went
        # (host clocks only — no device sync was added to produce these)
        stall = phase["data"] / dt if dt > 0 else 0.0
        out.update(
            steps=steps_run,
            data_wait_s=round(phase["data"], 4),
            dispatch_s=round(phase["dispatch"], 4),
            host_fetch_s=round(phase["fetch"], 4),
            data_stall_frac=round(stall, 4),
        )
        pct = timer.percentiles()
        if pct:
            out.update(
                step_time_p50=round(pct["p50"], 6),
                step_time_p95=round(pct["p95"], 6),
                step_time_p99=round(pct["p99"], 6),
            )
            rank0_print(
                f"  step p50/p95/p99 {pct['p50'] * 1e3:.1f}/"
                f"{pct['p95'] * 1e3:.1f}/{pct['p99'] * 1e3:.1f} ms, "
                f"data stall {stall:.1%}"
            )
        compile_d = max(counters_lib.get("compile.seconds") - compile_s0, 0.0)
        # MFU from the captured XLA flop count over the epoch's wall time
        # per step, compile seconds taken out. The drain above makes the
        # wall the device's; a lap between dispatches is host time under
        # run-ahead (its p50 printed 300-900% on 13-step epochs, PERF.md).
        # None on unknown chips (CPU emulation) — never a made-up figure.
        if self._step_cost and steps_run and dt > compile_d:
            mfu = costmodel_lib.mfu(
                self._step_cost.get("flops_per_step"),
                (dt - compile_d) / steps_run,
                self.n_devices,
            )
            if mfu is not None:
                out["mfu"] = mfu
                rank0_print(f"  MFU {mfu:.1%}")
        self._publish_memory_gauges()
        # goodput attribution for this epoch's wall time: the measured
        # stall + the compile/ckpt seconds that landed inside it, with the
        # remainder — the step loop actually stepping — as productive.
        # The in-epoch remainder definition keeps the ledger's sum-equals-
        # wall-clock invariant exact instead of approximately true.
        ckpt_d = max(self._goodput.window_value("ckpt") - ckpt_s0, 0.0)
        self._goodput.add("data_stall", phase["data"])
        self._goodput.add("compile", compile_d)
        self._goodput.add(
            "productive", dt - phase["data"] - compile_d - ckpt_d
        )
        counters_lib.inc("train.epochs")
        counters_lib.inc("train.steps", steps_run)
        t_end = spans_lib.add_timed(
            "train/epoch_tail", "train.epoch_tail_s", t_tail, epoch=epoch
        )
        spans_lib.add_event("train/epoch", t_epoch, t_end - t_epoch, epoch=epoch)
        return out

    def _train_epoch_fused(self, epoch: int) -> dict:
        """One jit call for the whole epoch (tpu_dist/train/epoch.py)."""
        cfg = self.cfg
        # no per-step grain inside the jit: an interrupt mid-epoch falls
        # back to the previous clean boundary
        self._progress = (self.state, epoch, 0, False)
        lr = self._lr(epoch)
        compile_s0 = counters_lib.get("compile.seconds")
        t0 = time.time()
        t_pc = time.perf_counter()
        self.state, metrics = self._fused_runner(
            self.state, *self._fused_data, lr, epoch
        )
        m = _fetch_metrics(metrics)  # one transfer; blocks on completion
        # the fused epoch has no step grain: one span covers the whole
        # compiled call (compile included on its first trip)
        spans_lib.add_event(
            "train/fused_epoch", t_pc, time.perf_counter() - t_pc, epoch=epoch
        )
        counters_lib.inc("train.epochs")
        if self._heartbeat is not None:
            self._heartbeat.beat(epoch=epoch, phase="fused_epoch", force=True)
        if self._flight is not None:
            # the fused path's only grain: one step slot per epoch call
            self._flight.step(epoch, None)
        if cfg.nan_guard and not np.isfinite(m["loss"]):
            raise TrainingDivergedError(
                f"non-finite loss {m['loss']} in fused epoch {epoch} (lr={lr}); "
                f"restore from ckpt_dir to recover"
            )
        dt = time.time() - t0
        n_images = int(self._fused_data[0].shape[0])
        ips = n_images / dt if dt > 0 else 0.0
        rank0_print(
            f"Epoch:[{epoch}/{cfg.epochs}] (fused) lr={lr:.5f} "
            f"loss={m['loss']:.4f} acc1={m['acc1']:.2f} acc5={m['acc5']:.2f}"
        )
        rank0_print(f"Epoch {epoch} done in {dt:.2f}s ({ips:.0f} img/s)")
        # device-resident data: there IS no input pipeline to stall on
        m.update(epoch_time=dt, images_per_sec=ips, data_stall_frac=0.0)
        # cost/MFU: XLA counts the epoch program's step-scan body ONCE, so
        # the raw count already IS per-step flops (loop_trips=1 — the
        # epoch-level shuffle/pad epilogue is the only omission); the wall
        # side normalizes to one step by the trip count
        from tpu_dist.train.epoch import fused_steps_per_epoch  # noqa: PLC0415

        trips = fused_steps_per_epoch(n_images, cfg.batch_size)
        self._capture_step_cost(
            self.state, *self._fused_data, lr, epoch,
            runner=self._fused_runner, loop_trips=1,
        )
        if self._step_cost and self._step_traced:
            # MFU only from compile-free epochs: the first fused call's dt
            # includes the whole-epoch XLA compile (often several epochs'
            # worth of wall time), and a 5-10x-understated epoch-0 MFU
            # would pollute mfu_mean and the compare gate — the same
            # discipline as the per-step path's warmup-excluded p50
            mfu = costmodel_lib.mfu(
                self._step_cost.get("flops_per_step"), dt / trips,
                self.n_devices,
            )
            if mfu is not None:
                m["mfu"] = mfu
                rank0_print(f"  MFU {mfu:.1%}")
        self._step_traced = True
        self._publish_memory_gauges()
        # goodput: device-resident data means no stall bucket; the whole
        # call minus its compile time is productive step time
        compile_d = max(counters_lib.get("compile.seconds") - compile_s0, 0.0)
        self._goodput.add("compile", compile_d)
        self._goodput.add("productive", dt - compile_d)
        # anomaly detection at the only grain the fused path has (the
        # epoch-mean loss); no per-step norms here — --device_metrics is
        # refused with --fused_epoch at construction
        self._observe_health(epoch, None, 0, m)
        if preemption.requested():
            # the fused epoch has no step grain — the epoch boundary is the
            # first cooperative point a SIGTERM can be honored at. The epoch
            # IS complete here (metrics fetched above block on it), so
            # publish that before raising: _emergency_save must file the
            # state under THIS epoch, not discard it as "0 steps done"
            self._progress = (self.state, epoch, 0, True)
            raise PreemptedError(
                f"SIGTERM observed during fused epoch {epoch} — shutting "
                f"down at the epoch boundary"
            )
        return m

    def _lr(self, epoch: int) -> float:
        """Scheduled LR times the auto-recovery backoff scale."""
        return self.lr_schedule(epoch) * self._lr_scale

    def _capture_step_cost(self, *args, runner=None, loop_trips=None) -> None:
        """ONE XLA cost-analysis capture per Trainer (obs/costmodel.py):
        an abstract host-side re-trace of the already-compiled step —
        no device dispatch, no second compile — published as the
        ``device.flops_per_step``/``device.bytes_per_step`` gauges and
        held for the per-epoch MFU. ``{}`` marks a failed capture so it
        is never retried in the hot loop."""
        if self._step_cost is not None:
            return
        cost = costmodel_lib.analyze_jitted(
            runner if runner is not None else self.train_step,
            *args,
            loop_trips=(
                loop_trips if loop_trips is not None
                else self.cfg.grad_accu_steps
            ),
        )
        self._step_cost = cost or {}
        costmodel_lib.publish(cost)
        self._capture_memory_ledger(
            runner if runner is not None else self.train_step, args
        )

    def _capture_memory_ledger(self, jitted, args) -> None:
        """ONE HBM-ledger snapshot per Trainer, at first dispatch beside
        the flops capture (obs/memory.py): the live-buffer census
        reconciled against the allocator (attributed + unattributed ==
        bytes_in_use, exact), the construction-time static ledger, and —
        when telemetry consumers exist — the ``memory_analysis()``
        waterfall of the step, which costs one extra host-side AOT
        compile (booked into ``compile.seconds`` by the monitoring
        listener) and is therefore skipped on telemetry-less runs.
        Published as ``mem.*`` gauges and one ``memory`` history record
        (schema v11)."""
        if self._mem_record is not None:
            return
        from tpu_dist.obs import memory as memory_lib  # noqa: PLC0415

        xla = None
        if self._history is not None or self._exporter is not None:
            xla = costmodel_lib.memory_analysis_jitted(jitted, *args)
        rec = memory_lib.ledger(static=self._mem_static, xla=xla)
        if self._mem_feasibility:
            rec["feasibility"] = self._mem_feasibility
        memory_lib.publish_ledger(rec)
        self._mem_record = rec
        if self._history is not None:
            self._history.log("memory", **rec)
        rank0_print("=> " + memory_lib.summary_line(rec))

    def _publish_memory_gauges(self) -> None:
        """Epoch-grain peak-HBM gauges from the runtime allocator's own
        counters (the true device numbers on TPU/GPU, now across ALL
        local devices — the scalar keys are the WORST chip, with min/
        skew gauges beside them; nothing is published on CPU, where the
        backend keeps no stats). ``mem.headroom_frac`` — the fraction of
        the worst chip's limit that its peak (``mem.peak_bytes``: buffers
        plus the executables' reserved temporaries) left free — feeds the
        built-in ``memory_headroom_low`` alert rule."""
        mem = costmodel_lib.device_memory_stats()
        if mem:
            for key, value in mem.items():
                counters_lib.set_gauge(f"mem.{key}", value)
            lim = mem.get("bytes_limit")
            use = mem.get("peak_bytes", mem.get("bytes_in_use"))
            if lim and isinstance(use, (int, float)):
                counters_lib.set_gauge(
                    "mem.headroom_frac", round(1.0 - use / lim, 4)
                )

    def _observe_health(self, epoch: int, step, nb: int, m: dict) -> None:
        """Per-fetch health layer over the metrics the loop already holds
        on the host — zero additional device traffic (TD107's fetch-count
        half). Writes the ``device_stats`` history record, per-step
        TensorBoard scalars, and feeds the anomaly detector; findings
        become rank-0 warnings + ``anomaly`` records, and
        ``--anomaly_action snapshot`` writes an exact mid-epoch
        checkpoint (emergency-snapshot discipline) while the state is
        still finite. The detector state is deterministic and the fed
        values are replica-identical (post-pmean), so every process takes
        the same snapshot branch — the collective save stays aligned."""
        cfg = self.cfg
        history = self._history
        if history is not None and "grad_norm" in m:
            history.log(
                "device_stats", epoch=epoch, step=step,
                **{
                    k: m[k]
                    for k in (
                        "grad_norm", "param_norm", "update_ratio",
                        "nonfinite_grads",
                    )
                    if k in m
                },
            )
        if self._tb is not None and step is not None:
            gs = epoch * nb + step
            self._tb.add_scalar("step/loss", m["loss"], gs)
            for k in ("grad_norm", "update_ratio"):
                if k in m:
                    self._tb.add_scalar(f"step/{k}", m[k], gs)
        # live layer at the fetch cadence: the health norms land in the
        # next exposition, and the step-grain alert rules (grad-norm
        # ceiling) see the SAME host copy — zero additional device traffic
        if self._exporter is not None:
            for k in ("grad_norm", "param_norm", "update_ratio"):
                if k in m:
                    self._export_rollup[f"device.{k}"] = m[k]
        if self._alerts is not None:
            fired = self._alerts.observe(m)
            if fired:
                self._fire_alerts(fired, epoch, step)
        if self._anomaly is None:
            return
        findings = self._anomaly.observe(
            epoch=epoch, step=step, loss=m.get("loss"),
            grad_norm=m.get("grad_norm"), nonfinite=m.get("nonfinite_grads"),
        )
        for f in findings:
            rank0_print(
                f"WARNING: anomaly {f['anomaly']} at epoch {epoch} step "
                f"{step}: value {f.get('value')}"
                + (
                    f" = {f['ratio']}x the rolling median {f['median']}"
                    if f.get("ratio") is not None else ""
                )
            )
            if history is not None:
                history.log("anomaly", **f)
            if self._flight is not None:
                self._flight.record(
                    "anomaly", anomaly=f["anomaly"], epoch=epoch, step=step,
                )
            counters_lib.inc("anomaly.findings")
            if (
                self._profiler is not None
                and "anomaly" in self._profile_triggers
                and mesh_lib.is_primary()
            ):
                # arm a bounded device capture: the NEXT steps — the ones
                # that explain whether the spike was data or numerics —
                # land on an XLA timeline (obs/profile.py caps apply)
                self._profiler.arm(f"anomaly_{f['anomaly']}")
            if (
                cfg.anomaly_action == "snapshot"
                and cfg.ckpt_dir
                and f["anomaly"] in ("loss_spike", "grad_norm_explosion")
            ):
                # pre-divergence forensic snapshot: the spike kinds fire
                # on FINITE values only, so the state is still safe to
                # publish. Written OFF the ckpt_{N} namespace (no "ckpt_"
                # substring — the discovery regexes cannot match it), so
                # the next periodic/end-of-epoch save can never overwrite
                # it, prune never removes it, and resume never silently
                # picks it — the pre-divergence bits stay on disk for as
                # long as the operator wants them. Stamped with the
                # finding + the exact position (mid_epoch_* for the
                # streaming path; the fused path's only grain is the
                # epoch boundary), so a manual rollback knows where it
                # re-enters. Synchronous plain write even under
                # --async_ckpt: a rare forensic event, not hot-path I/O.
                extra = {**self._ckpt_meta(), "anomaly": f["anomaly"]}
                if step is not None:
                    extra.update(self._mid_epoch_position(step + 1))
                stem = f"anomaly_{epoch}" + (
                    f"_s{step + 1}" if step is not None else ""
                )
                with self._goodput.timed("ckpt"):
                    if cfg.sharded_ckpt:
                        ckpt_lib.save_sharded(
                            cfg.ckpt_dir, self.state, epoch,
                            extra_meta=extra, stem=stem,
                        )
                    else:
                        ckpt_lib.save(
                            cfg.ckpt_dir, self.state, epoch,
                            extra_meta=extra, name=f"{stem}.npz",
                        )
                counters_lib.inc("anomaly.snapshots")
                rank0_print(
                    f"=> anomaly snapshot written ({stem}, epoch {epoch}"
                    + (f" step {step + 1}" if step is not None else "")
                    + ") — pre-divergence state preserved off the resume "
                    "namespace"
                )

    def _export_live(self, force: bool = False) -> None:
        """Publish one OpenMetrics exposition (``obs/export.py``): the
        counter registry, the latest epoch rollup + health norms, the
        goodput totals so far, the heartbeat age, and the per-rule
        ``alert_active`` gauges. Throttled HERE (not just in the writer)
        so the per-step cost inside the window is one clock read — the
        render/snapshot work only happens when something will publish."""
        if self._exporter is None:
            return
        now = time.monotonic()
        if not force and now - self._export_t < self._exporter.min_interval:
            return
        self._export_t = now
        values = dict(counters_lib.snapshot())
        values.update(self._export_rollup)
        # run-level goodput totals over the closed windows so far — the
        # same numbers the ledger's final record will carry
        totals = self._goodput.run_totals()
        for b in goodput_lib.ALL_BUCKETS:
            values[f"goodput.{b}_s"] = totals[f"{b}_s"]
        values["goodput.goodput_frac"] = totals["goodput_frac"]
        if self._heartbeat is not None:
            age = self._heartbeat.age()
            if age != float("inf"):
                values["heartbeat.age_s"] = round(age, 3)
        labeled = (
            {"alert_active": self._alerts.active()}
            if self._alerts is not None else None
        )
        self._exporter.update(values, labeled, force=True)

    def _epoch_live_update(self, epoch: int, last: dict) -> None:
        """Close of an epoch for the live layer: refresh the exporter's
        rollup (throughput, percentiles, stall, MFU, eval top-1), run the
        epoch-grain alert rules over the rollup + goodput fraction +
        counter snapshot (the delta rules — mid-run retraces — read the
        monotonic counters), and force an exposition so a scraper sees
        the epoch boundary immediately."""
        rollup = self._export_rollup
        rollup["train.epoch"] = epoch
        for key in ("images_per_sec", "loss", "mfu", "data_stall_frac",
                    "epoch_time"):
            if isinstance(last.get(key), (int, float)):
                rollup[f"train.{key}"] = last[key]
        for key in ("step_time_p50", "step_time_p95", "step_time_p99"):
            if isinstance(last.get(key), (int, float)):
                rollup[f"train.{key}_s"] = last[key]
        if isinstance(last.get("val_top1"), (int, float)):
            rollup["eval.top1"] = last["val_top1"]
        if self._alerts is not None:
            window = {
                k: v for k, v in last.items() if isinstance(v, (int, float))
            }
            window["goodput_frac"] = self._goodput.run_totals()["goodput_frac"]
            window.update(counters_lib.snapshot())
            fired = self._alerts.observe(window)
            if fired:
                self._fire_alerts(fired, epoch, None)
        self._export_live(force=True)

    def _fire_alerts(self, fired: list, epoch: int, step) -> None:
        """A rule fired: rank-0 warning + ``alert`` history record
        (schema v5) + counter + exporter gauge flip (the next exposition
        carries ``alert_active{rule=...} 1``) + — for ``profile = true``
        rules — an armed triggered-profiler capture, so the steps that
        explain the breach land on an XLA timeline."""
        for a in fired:
            counters_lib.inc("alerts.fired")
            rank0_print(
                f"WARNING: ALERT {a['rule']}: {a['metric']} = {a['value']} "
                f"{a['op']} threshold {a['threshold']} (sustained "
                f"{a['sustained']} window(s))"
            )
            if self._flight is not None:
                self._flight.record(
                    "alert", rule=a["rule"], epoch=epoch,
                    **({"step": step} if step is not None else {}),
                )
            if self._history is not None:
                extra = {"epoch": epoch}
                if step is not None:
                    extra["step"] = step
                self._history.log("alert", **extra, **a)
            if (
                a.get("profile")
                and self._profiler is not None
                and mesh_lib.is_primary()
            ):
                self._profiler.arm(f"alert_{a['rule']}")
        if self._exporter is not None:
            self._export_live(force=True)

    def _note_profile_event(self, ev: dict, epoch: int, step) -> None:
        """A triggered-profiler window opened/closed/failed: rank-0 line +
        a ``profile`` history record (schema v4), so ``obs summarize`` and
        the pod report can say WHEN and WHY each capture ran. A stop
        event carrying the auto-analysis (obs/profile.py hook) peels it
        off into its own ``profile_analysis`` record + summary line +
        calibration gauges — the ``profile`` record stays the small
        when/why stamp it always was."""
        ev = dict(ev)
        analysis = ev.pop("analysis", None)
        analysis_error = ev.pop("analysis_error", None)
        if ev.get("event") == "start":
            rank0_print(
                f"=> profiler capture started ({ev.get('reason')}) at "
                f"epoch {epoch} step {step} — {ev.get('window_steps')} "
                f"step window → {ev.get('dir')}"
            )
        elif ev.get("event") == "stop":
            rank0_print(
                f"=> profiler capture done ({ev.get('reason')}, "
                f"{ev.get('steps')} step(s)) → {ev.get('dir')}"
            )
        else:
            rank0_print(
                f"WARNING: profiler capture failed ({ev.get('reason')}): "
                f"{ev.get('error')} — triggered profiling disabled for "
                "this run"
            )
        if self._history is not None:
            self._history.log("profile", epoch=epoch, **ev)
        if ev.get("event") == "stop":
            self._note_capture_analysis(
                analysis, analysis_error, epoch=epoch,
                reason=ev.get("reason"), capture_dir=ev.get("dir"),
                steps=ev.get("steps"),
            )

    def _note_capture_analysis(
        self, analysis, error, *, epoch: int, reason, capture_dir, steps,
    ) -> None:
        """The read-back half of a capture (``obs/xprof.py``): rank-0
        attribution line, ``profile_analysis`` history record (schema
        v6), and cost-model calibration gauges (``cost.calibration_*`` —
        measured category seconds divided into the predicted per-step
        FLOPs/bytes). Analysis failures were counted by the hook
        already; here they surface as a warning + an error-stamped
        record, never an exception — forensics must not kill training."""
        if analysis is None:
            if error:
                rank0_print(
                    f"WARNING: capture analysis failed ({reason}): {error}"
                )
                if self._history is not None:
                    self._history.log(
                        "profile_analysis", epoch=epoch, reason=reason,
                        dir=capture_dir, error=error,
                    )
            return
        from tpu_dist.obs import xprof as xprof_lib  # noqa: PLC0415

        cal = costmodel_lib.calibration(
            self._step_cost, analysis,
            steps=steps, n_devices=jax.local_device_count(),
        )
        if cal:
            costmodel_lib.publish_calibration(cal)
        rank0_print(
            f"=> capture analysis ({reason}): "
            + xprof_lib.summary_line(analysis)
        )
        if self._history is not None:
            rec = dict(analysis)
            if cal:
                rec["calibration"] = cal
            if steps is not None:
                rec["steps"] = steps
            self._history.log(
                "profile_analysis", epoch=epoch, reason=reason,
                dir=capture_dir, **rec,
            )

    def _apply_step_faults(self, epoch: int, step: int, lr: float) -> None:
        """Host-side --fault_plan actions at the step grain. A matching
        ``sigterm`` clause delivered a real signal inside ``on_step`` (the
        loop's preemption check picks it up); ``nan_loss`` reports a
        divergence through the SAME error type the NaN guard uses, so the
        existing auto-recover machinery runs unmodified."""
        acts = faults.on_step(epoch, step, rank=mesh_lib.process_index())
        if faults.NAN_LOSS in acts:
            if self.cfg.nan_guard:
                raise TrainingDivergedError(
                    f"non-finite loss nan at epoch {epoch} step {step} "
                    f"(lr={lr}) [fault-injected]; restore from ckpt_dir to "
                    f"recover"
                )
            rank0_print(
                f"[faults] nan_loss injected at epoch {epoch} step {step} "
                "but --no_nan_guard is set — ignored"
            )

    def _quarantine_ckpt(self, path: str, err: Exception) -> None:
        """Rank-0 renames a failed checkpoint to ``*.corrupt`` (kept for
        forensics, invisible to every discovery function). Other processes
        only log — they will stop seeing the file once the rename lands."""
        if jax.process_index() == 0:
            try:
                dst = ckpt_lib.quarantine(path)
            except OSError:
                dst = path + ".corrupt (rename failed)"
        else:
            dst = path + ".corrupt"
        rank0_print(
            f"WARNING: checkpoint {path} failed integrity verification "
            f"({err}) — quarantined to {dst}; falling back to the next "
            "older checkpoint"
        )

    def _check_ladder_agreement(self, picked_epoch: int) -> None:
        """Multi-process resumes must agree on WHICH checkpoint the ladder
        picked: the walk runs per-process (reads and transient errors are
        local), and resuming different epochs on different processes is
        silent divergence — the one unacceptable outcome. Every process
        reaches this exact point once per _restore_latest (picked_epoch is
        -1 when nothing usable was found), so the allgather is safe."""
        if jax.process_count() <= 1:
            return
        from jax.experimental import multihost_utils  # noqa: PLC0415

        got = np.asarray(
            multihost_utils.process_allgather(np.int32(picked_epoch))
        ).ravel()
        if int(got.min()) != int(got.max()):
            raise RuntimeError(
                "processes disagree on the resume checkpoint (per-process "
                f"ladder picks: {sorted(set(int(x) for x in got))}) — a "
                "transient read error or racing quarantine made the "
                "newest-intact walk diverge; inspect ckpt_dir (quarantined "
                "*.corrupt files) and relaunch"
            )

    def _restore_latest(self):
        """Restore the newest INTACT checkpoint in the configured format.

        The retry ladder: walk newest→oldest; a candidate that is
        unreadable or fails its CRC32 stamps (``--ckpt_verify``, default
        on) is quarantined to ``*.corrupt`` with a rank-0 warning and the
        next older checkpoint is tried — a torn/bit-flipped newest file
        degrades the resume by one snapshot instead of bricking it.
        Config mismatches (pipeline layout, AdamW mask, mid-epoch
        batch/seed stamps, shape mismatches) still RAISE: those are
        operator errors, not corruption, and falling past them would
        silently resume the wrong run.

        Returns the restored epoch, or None when the dir holds nothing
        usable; raises when the dir holds only the OTHER format (a silent
        restart-from-scratch is the one unacceptable outcome)."""
        cfg = self.cfg
        if not cfg.ckpt_dir:
            return None
        if cfg.sharded_ckpt:
            list_, read_meta_, restore_ = (
                ckpt_lib.all_sharded_checkpoints,
                ckpt_lib.read_sharded_meta,
                ckpt_lib.restore_sharded,
            )
            # multi-process: deep (full-CRC) verification would have EVERY
            # process decompress the WHOLE checkpoint — n× the bytes the
            # sharded format exists to avoid. Shallow verify checks the
            # manifest/shard-set/zip directories; restore's own overlap
            # reads still surface piece-level corruption to the ladder.
            verify_ = functools.partial(
                ckpt_lib.verify_sharded, deep=jax.process_count() == 1
            )
            other = ckpt_lib.latest_checkpoint
        else:
            # plain format: verification is FUSED into restore's single
            # decompression pass (verify=True) — a standalone verify_npz
            # here would read the whole archive twice per resume
            list_, read_meta_, restore_, verify_ = (
                ckpt_lib.all_checkpoints,
                ckpt_lib.read_meta,
                functools.partial(ckpt_lib.restore, verify=cfg.ckpt_verify),
                None,
            )
            other = ckpt_lib.latest_sharded_checkpoint
        if jax.process_index() == 0:
            # a crash between open(tmp) and the atomic rename leaks a *.tmp
            # forever (plain npz, shard piece, or manifest alike); resume
            # startup is a no-write-in-flight point, so sweep here
            # (single-writer-per-file discipline)
            ckpt_lib.sweep_stale_tmp(cfg.ckpt_dir)
        candidates = list_(cfg.ckpt_dir)
        if not candidates:
            if other(cfg.ckpt_dir):
                raise ValueError(
                    f"ckpt_dir {cfg.ckpt_dir} holds checkpoints in the "
                    f"{'plain' if cfg.sharded_ckpt else 'sharded'} format "
                    f"but this run asked for the "
                    f"{'sharded' if cfg.sharded_ckpt else 'plain'} one — "
                    "flip --sharded_ckpt to match (the formats do not "
                    "auto-convert)"
                )
            self._check_ladder_agreement(-1)
            return None
        from tpu_dist.elastic import remap as elastic_remap  # noqa: PLC0415

        self._last_reshard_s = 0.0
        self._elastic_resume = None
        chosen = None
        for path, epoch in candidates:
            try:
                if cfg.ckpt_verify and verify_ is not None:
                    verify_(path)
                meta = read_meta_(path)
            except (ckpt_lib.CheckpointCorruptError,) + ckpt_lib.CKPT_READ_ERRORS as e:
                self._quarantine_ckpt(path, e)
                continue
            # config-mismatch checks on the (readable) meta: a valid-but-
            # wrong checkpoint must raise (ConfigMismatchError), not be
            # quarantined as corrupt
            self._check_ckpt_meta(meta, path)
            # mesh-shape portability: restore WITH the elastic remapper —
            # world-size-independent leaves load verbatim; the dp-extent-
            # dependent flat layouts (ZeRO-1 opt vectors, EF residuals)
            # are remapped onto THIS run's extent (elastic/remap.py).
            # A model-shape mismatch still raises (ConfigMismatchError,
            # from make_remapper's params_len check or the restore).
            remapper = elastic_remap.make_remapper(
                self.state, meta, self.n_data
            )
            t_restore = time.monotonic()
            try:
                with spans_lib.span("ckpt/restore_ladder", file=path):
                    restored = restore_(path, self.state, remap=remapper)
            except (ckpt_lib.CheckpointCorruptError,) + ckpt_lib.CKPT_READ_ERRORS as e:
                # plain format verifies CRCs HERE (fused into restore's
                # read); sharded piece-level corruption also lands here
                self._quarantine_ckpt(path, e)
                continue
            if remapper.used:
                # this restore WAS the reshard: charge its wall time to the
                # goodput recovery bucket (the __init__ caller splits it
                # out of the ckpt bucket) and count it
                self._last_reshard_s = time.monotonic() - t_restore
                counters_lib.inc("resume.resharded")
                prev_dp = (meta.get("elastic") or {}).get("dp")
                rank0_print(
                    f"=> elastic resume: remapped {len(remapper.used)} "
                    f"dp-extent-dependent leaf(s) from dp={prev_dp} onto "
                    f"dp={self.n_data} (ZeRO-1/EF flat layouts re-laid — "
                    "docs/resilience.md 'Elastic training')"
                )
            chosen = (path, epoch, meta, restored, bool(remapper.used))
            break
        self._check_ladder_agreement(chosen[1] if chosen is not None else -1)
        if chosen is None:
            rank0_print(
                f"WARNING: every checkpoint in {cfg.ckpt_dir} was corrupt "
                "and has been quarantined — starting from scratch"
            )
            return None
        path, epoch, meta, restored, resharded = chosen
        stamped_dp = (meta.get("elastic") or {}).get("dp")
        if isinstance(stamped_dp, int) and stamped_dp < self.n_data:
            # the scale-up half: a resume onto a LARGER extent is a grow
            # (probe-triggered or fleet-granted) — counted whether or not
            # the remapper had leaves to re-lay (a run without ZeRO-1/EF
            # state grows with zero remapped leaves but it still grew)
            counters_lib.inc("elastic.grows")
        self.state = self._place_state(restored)
        # pick the recovery backoff up from the checkpoint (see _ckpt_meta)
        self._lr_scale = float(meta.get("lr_scale", 1.0))
        # exact mid-epoch snapshot (emergency save): re-enter THIS epoch at
        # this step instead of starting the next epoch
        self._resume_step = int(meta.get("mid_epoch_step", 0))
        self._resume_examples = 0
        # the snapshot's final-step metrics (when stamped): replayed by
        # train_epoch iff the resumed epoch has zero steps left to run
        self._resume_metrics = (
            meta.get("mid_epoch_metrics") if self._resume_step else None
        )
        if self._resume_step:
            from tpu_dist.elastic.errors import (  # noqa: PLC0415
                ConfigMismatchError,
            )

            # the GLOBAL batch size and shuffle seed pin the data position
            # under ANY world size — refuse silent drift (same contract as
            # the pp/adamw layout stamps above)
            for key, current in (
                ("mid_epoch_batch_size", cfg.batch_size),
                ("mid_epoch_seed", cfg.seed or 0),
            ):
                saved = meta.get(key)
                if saved is not None and saved != current:
                    raise ConfigMismatchError(
                        f"checkpoint {path} is a mid-epoch snapshot taken "
                        f"with {key.removeprefix('mid_epoch_')}={saved}; "
                        f"this run uses {current} — the step offset would "
                        f"re-enter the epoch at the wrong data position "
                        f"(silently skipping/repeating examples). Resume "
                        f"with the matching value, or from the last clean "
                        f"epoch checkpoint."
                    )
            # world-portable re-entry: the per-shard step offset replays
            # bit-identically only when the shard count is unchanged AND
            # the snapshot itself entered its epoch at offset 0. Otherwise
            # switch to the consumed-example offset: skip the globally
            # consumed prefix and re-partition the remainder over THIS
            # world's shards (sampler.set_offset — nothing dropped or
            # double-seen; augmentation streams re-key, so the continued
            # trajectory is parity, not bit-identity).
            nproc = mesh_lib.process_count()
            saved_procs = meta.get("mid_epoch_procs")
            saved_ex = meta.get("mid_epoch_examples")
            same_world = saved_procs is None or int(saved_procs) == nproc
            offset_free = (
                saved_ex is None
                or int(saved_ex) == self._resume_step * cfg.batch_size
            )
            if not (same_world and offset_free):
                # clamp defensively too (pre-clamp or foreign stamps): an
                # offset at N is a legally-empty resumed epoch, past N is
                # not a position in this dataset
                self._resume_examples = min(
                    int(
                        saved_ex
                        if saved_ex is not None
                        else self._resume_step * cfg.batch_size
                    ),
                    len(self.train_data[0]),
                )
                self._resume_step = 0
        self._state_poisoned = False
        self._elastic_resume = {
            "epoch": epoch,
            "world": mesh_lib.process_count(),
            "dp": self.n_data,
            "resharded": resharded,
            "prev_dp": (meta.get("elastic") or {}).get("dp"),
            "prev_procs": (meta.get("elastic") or {}).get("procs"),
            "mid_epoch_step": self._resume_step,
            "examples_offset": self._resume_examples,
        }
        if self._resume_step:
            self._progress = (self.state, epoch, self._resume_step, False)
            rank0_print(
                f"=> resumed from {path} (mid-epoch {epoch}, "
                f"continuing at step {self._resume_step})"
            )
        elif self._resume_examples:
            self._progress = (self.state, epoch, 0, False)
            rank0_print(
                f"=> resumed from {path} (mid-epoch {epoch}, elastic: "
                f"continuing at example offset {self._resume_examples}, "
                f"remainder re-partitioned over {mesh_lib.process_count()} "
                "process(es))"
            )
        else:
            self._progress = (self.state, epoch, 0, True)
            rank0_print(f"=> resumed from {path} (epoch {epoch})")
        return epoch

    def _auto_recover(self, err: TrainingDivergedError) -> None:
        """Divergence response (--auto_recover): reload the last good
        checkpoint and back the LR schedule off by cfg.recover_lr_factor —
        a bare retry would diverge identically (epoch-seeded data order is
        deterministic by design). Raises the original error when there is
        no checkpoint to fall back to."""
        cfg = self.cfg
        self._ckpt_close(suppress=True)  # drain in-flight async writes
        epoch = self._restore_latest()
        if epoch is None:
            raise err
        # a mid-fit recovery is not a new segment: fit's resume-record
        # block already ran, and leaving this set would leak a stale
        # 'resume' boundary into a LATER fit() on this instance (the
        # auto_recover history record documents this restore instead)
        self._elastic_resume = None
        self.start_epoch = (
            epoch if (self._resume_step or self._resume_examples) else epoch + 1
        )
        self._seed_global_step()  # the --profile_steps grid follows the
        #                           restored (replayed) training position
        self._lr_scale *= cfg.recover_lr_factor
        rank0_print(
            f"=> AUTO-RECOVER: {err}; resumed from epoch {epoch}, LR scale "
            f"now {self._lr_scale:g} (factor {cfg.recover_lr_factor})"
        )

    def fit(self, epochs: Optional[int] = None) -> dict:
        cfg = self.cfg
        epochs = epochs if epochs is not None else cfg.epochs
        from tpu_dist.metrics.history import MetricsHistory  # noqa: PLC0415

        run_id = self._run_id  # stamped at construction (one id per run)
        # rel_s shares the construction-time clock origin with the span
        # recorder — one timeline for epoch bars and host spans.
        # --per_host_log: EVERY process writes its own history (rank 0
        # keeps the bare path, rank k appends .h<k>) so `obs pod` can
        # merge per-host goodput ledgers and skew timelines later.
        log_path = cfg.log_file
        if cfg.per_host_log and cfg.log_file:
            from tpu_dist.obs.heartbeat import per_rank_path  # noqa: PLC0415

            log_path = per_rank_path(cfg.log_file, jax.process_index())
        history = MetricsHistory(
            log_path, run_id=run_id, t0=self._telemetry_t0,
            all_processes=cfg.per_host_log,
        )
        # the step loop's health records (device_stats / anomaly) write
        # through this handle; cleared in the finally below so a direct
        # train_epoch() call outside fit() never logs to a closed file
        self._history = history
        # crash forensics (docs/observability.md "Crash forensics"): a
        # per-rank SIGKILL-surviving flight ring + faulthandler stack
        # capture, armed on EVERY process — unlike the rank-0 telemetry,
        # forensics is per-rank by definition (any rank can wedge)
        self._flight = None
        self._fault_handle = None
        if cfg.crash_dir:
            from tpu_dist.obs import flight as flight_lib  # noqa: PLC0415
            from tpu_dist.obs.heartbeat import per_rank_path  # noqa: PLC0415

            import os as _fos  # noqa: PLC0415

            rank = jax.process_index()
            self._flight = flight_lib.FlightRecorder(
                per_rank_path(
                    _fos.path.join(cfg.crash_dir, flight_lib.RING_NAME), rank
                ),
                run_id=run_id, rank=rank,
            )
            # last-words discipline: an UNHANDLED exception anywhere (main
            # thread or a worker like the loader producer) stamps a fatal
            # slot before the interpreter dies; previous hooks still run
            self._flight.install_excepthooks()
            # every host span OPEN (ckpt write/restore, loader produce,
            # eval) taps one slot — the ring shows which host operation
            # was in flight at death, on every rank, buffering none
            spans_lib.set_open_listener(self._flight.span_open)
            self._flight.record(
                "open", epoch=self.start_epoch,
                world=mesh_lib.process_count(), dp=self.n_data,
            )
            # hard-fault tracebacks land in the per-rank crash file, and
            # SIGUSR1 dumps all threads on demand — the launcher watchdog
            # signals a live-but-frozen rank and reads back WHERE it is
            # stuck before escalating SIGTERM→SIGKILL
            self._fault_handle = flight_lib.arm_faulthandler(
                per_rank_path(
                    _fos.path.join(cfg.crash_dir, flight_lib.STACKS_NAME),
                    rank,
                )
            )
        # elastic observability (docs/resilience.md "Elastic training"):
        # the current world size is a first-class gauge (segment
        # boundaries in summarize/tail/pod key off it) and a supervisor-
        # relaunched child reports WHICH restart it is (the launcher
        # injects TPU_DIST_ELASTIC_RESTARTS into every relaunched round)
        import os as _os  # noqa: PLC0415

        counters_lib.set_gauge("elastic.world_size", self.n_data)
        try:
            _restarts = int(
                _os.environ.get("TPU_DIST_ELASTIC_RESTARTS", "0") or 0
            )
        except ValueError:
            _restarts = 0
        if _restarts:
            counters_lib.set_gauge("elastic.restarts", _restarts)
        # causal arbitration tracing (schema v15): a relaunch that
        # actuates a fleet decision carries the scheduler's id/cause in
        # env (launcher reads them off the allocation file) — stamped
        # into the resume record, the flight-ring slot, and the
        # fleet.decision_id gauge, so every artifact layer names WHICH
        # arbitration moved this run (a chip-loss relaunch has none)
        _decision_id: "Optional[int]" = None
        _decision_cause = _os.environ.get("TPU_DIST_FLEET_DECISION_CAUSE") or None
        try:
            _raw_did = _os.environ.get("TPU_DIST_FLEET_DECISION_ID", "")
            _decision_id = int(_raw_did) if _raw_did else None
        except ValueError:
            _decision_id = None
        if _decision_id is not None:
            counters_lib.set_gauge("fleet.decision_id", _decision_id)
        if self._elastic_resume is not None:
            # one 'resume' record per resumed segment (schema v7): world
            # size, reshard flag, re-entry position — the segment-boundary
            # line obs summarize/tail/pod render
            _trace = {}
            if _decision_id is not None:
                _trace["decision_id"] = _decision_id
                if _decision_cause:
                    _trace["decision_cause"] = _decision_cause
            history.log(
                "resume", restarts=_restarts, **_trace,
                **self._elastic_resume,
            )
            if self._flight is not None:
                self._flight.record(
                    "resume",
                    epoch=self._elastic_resume.get("epoch"),
                    world=self._elastic_resume.get("world"),
                    dp=self._elastic_resume.get("dp"),
                    resharded=self._elastic_resume.get("resharded"),
                    **_trace,
                )
            self._elastic_resume = None
        # re-arm host-span tracing (construction armed it before the
        # resume-path restore; a second fit() on this Trainer re-arms after
        # _export_telemetry disarmed) WITHOUT clearing or re-zeroing — the
        # restore ladder's spans are still in the buffer and the clock
        # origin must stay the construction instant. Counters are always
        # live — they are plain host ints.
        telemetry = self._telemetry
        if telemetry:
            spans_lib.enable(fresh=False)
            counters_lib.set_gauge("run.id", run_id)
            counters_lib.set_gauge("run.grad_compression", cfg.grad_compression)
            if not cfg.fsdp:  # under fsdp the wire format is inert (GSPMD)
                # static ring-model estimate, pure host arithmetic from the
                # param SHAPES (no device touch): RS+AG = 2 payload legs ×
                # bytes/elem of the wire format. The exact per-eqn account
                # is TD104's job; this gauge puts the mode's wire cost next
                # to the throughput numbers in every history record.
                import math  # noqa: PLC0415

                n_params = sum(
                    math.prod(l.shape) if l.shape else 1
                    for l in jax.tree_util.tree_leaves(self.state.params)
                )
                bpe = {"none": 4, "bf16": 2, "int8": 1, "int8_ef": 1}[
                    cfg.grad_compression
                ]
                counters_lib.set_gauge(
                    "comm.grad_wire_bytes_per_step", 2 * bpe * n_params
                )
        if cfg.heartbeat_file:
            from tpu_dist.obs.heartbeat import (  # noqa: PLC0415
                Heartbeat, per_rank_path,
            )

            # EVERY process beats its own file (per_rank_path: rank 0 the
            # bare path, rank k .h<k> — the --per_host_log naming):
            # liveness is per-host, and a watchdog that only sees rank 0
            # would kill healthy workers / miss a wedged rank 3. The
            # launcher's --heartbeat_dir watchdog reads the same scheme.
            self._heartbeat = Heartbeat(
                per_rank_path(cfg.heartbeat_file, jax.process_index())
            )
            self._heartbeat.beat(
                epoch=self.start_epoch, phase="start", force=True
            )
        # live export + alerting (docs/observability.md "Live export"):
        # the exporter publishes the counter registry + the latest epoch
        # rollup as OpenMetrics (textfile at the heartbeat's step-grain
        # throttle, rank-0 HTTP endpoint serving the last snapshot); the
        # alert engine evaluates the declarative rules at the epoch grain
        # (stall/MFU/goodput/retraces) and the step-fetch grain (norms).
        # Host-side only — TD109 pins the traced step byte-identical.
        self._exporter = None
        self._alerts = None
        self._export_rollup = {}
        self._export_t = float("-inf")
        if cfg.metrics_file or cfg.metrics_port:
            from tpu_dist.obs.export import MetricsExporter  # noqa: PLC0415
            from tpu_dist.obs.heartbeat import per_rank_path  # noqa: PLC0415

            rank = jax.process_index()
            textfile = (
                per_rank_path(cfg.metrics_file, rank)
                if cfg.metrics_file else None
            )
            # the HTTP endpoint is rank-0-only (MetricsExporter refuses a
            # port on rank >= 1); other ranks export via their derived
            # textfile only — and with --metrics_port alone, rank >= 1 has
            # NO output surface, so it skips the exporter entirely rather
            # than render expositions nothing can read
            port = (cfg.metrics_port or None) if rank == 0 else None
            if textfile or port:
                self._exporter = MetricsExporter(
                    textfile=textfile, port=port, rank=rank
                )
        if self._alert_rule_list:
            from tpu_dist.obs.alerts import AlertEngine  # noqa: PLC0415

            # fresh streak/cooldown state per fit(); runs on EVERY process
            # (like the anomaly detector) — its actions are rank-scoped
            # (rank-0 history/warning, per-process exporter gauges), never
            # collective, so per-host metric divergence is harmless
            self._alerts = AlertEngine(self._alert_rule_list)
            # delta rules (mid-run retraces) fire on change SINCE FIT
            # START — a counter born mid-run must alert on its first
            # increment, not spend it establishing a baseline
            self._alerts.seed_deltas(counters_lib.snapshot())
        last = {}
        self._last_epoch = self.start_epoch
        self._in_epoch = False
        self._tb = None
        if cfg.tensorboard_dir and mesh_lib.is_primary():
            from tpu_dist.metrics.tensorboard import SummaryWriter  # noqa: PLC0415

            self._tb = SummaryWriter(cfg.tensorboard_dir)
        attempts = cfg.auto_recover
        self._best_top1 = -1.0  # survives recovery retries of _fit_loop
        # preemption-graceful shutdown: SIGTERM sets a flag; the loops honor
        # it at the step/epoch grain and raise PreemptedError (restored to
        # the previous disposition on every exit path below)
        sig_token = preemption.install()
        preemption.clear()
        try:
            while True:
                try:
                    result = self._fit_loop(epochs, history, last)
                    with self._goodput.timed("ckpt"):
                        # success path: writer errors RAISE; the blocking
                        # drain of background writes is ckpt time (the
                        # ledger's sum-to-wall partition stays exact)
                        self._ckpt_close()
                    if self._heartbeat is not None:
                        # clean exit: the heartbeat's ABSENCE is the signal
                        self._heartbeat.sweep()
                    return result
                except TrainingDivergedError as e:
                    # from here until the restore completes, self.state is
                    # NaN-poisoned — _emergency_save must not snapshot it
                    self._state_poisoned = True
                    if attempts <= 0:
                        raise
                    attempts -= 1
                    with self._goodput.timed("recovery"):
                        self._auto_recover(e)  # raises e when no ckpt to load
                    history.log(
                        "auto_recover", epoch=self._last_epoch,
                        lr_scale=self._lr_scale,
                    )
                    if self._flight is not None:
                        self._flight.record(
                            "auto_recover", epoch=self._last_epoch,
                        )
        except (KeyboardInterrupt, PreemptedError) as e:
            # Ctrl-C and SIGTERM share one snapshot discipline; the caller
            # (cli/train.py) maps PreemptedError to the distinct
            # PREEMPTION_EXIT_CODE so the launcher/orchestrator can requeue
            if isinstance(e, PreemptedError):
                counters_lib.inc("preemption.observed")
            # preemption/interrupt-loss accounting: the shutdown tail this
            # process spends honoring the signal (position beat + emergency
            # snapshot), measured from HERE — time between the SIGTERM and
            # the cooperative boundary stays in the bucket that actually
            # used it (finishing the step/eval, or unattributed for a
            # partial epoch), so the ledger's sum-equals-wall-clock
            # invariant holds with no double count. The restart gap is the
            # offline half (obs/goodput.py run_ledger).
            t_pre = time.monotonic()
            if self._heartbeat is not None:
                # last beat marks the position; the file is deliberately
                # NOT swept — a watchdog seeing it + the exit code knows
                # the run ended preempted/interrupted, not hung
                self._heartbeat.beat(
                    epoch=self._last_epoch, phase="preempted", force=True
                )
            self._emergency_save()
            self._goodput.add("preempt", time.monotonic() - t_pre)
            raise
        finally:
            # error exits (divergence, interrupt): still drain in-flight
            # writes, but log writer failures rather than mask the
            # propagating exception
            preemption.restore(sig_token)
            with self._goodput.timed("ckpt"):  # async-writer drain is ckpt time
                self._ckpt_close(suppress=True)
            if self._profiler is not None:
                # an in-flight capture window must not outlive the run
                ev = self._profiler.close()
                if ev is not None:
                    self._note_profile_event(ev, self._last_epoch, None)
            if self._tb is not None:
                self._tb.close()
            self._close_goodput(history)
            if self._exporter is not None:
                # one final forced exposition — the closing totals stay
                # scrapeable in the textfile (deliberately not deleted:
                # the last exposition documents how the run ended) — then
                # stop the HTTP thread
                try:
                    self._export_live(force=True)
                finally:
                    self._exporter.close()
                    self._exporter = None
            self._alerts = None
            if telemetry:
                self._export_telemetry(history)
            # OOM forensics (obs/memory.py): a propagating
            # RESOURCE_EXHAUSTED is parsed into a typed allocation
            # report, logged as a 'memory' OOM event (schema v11) while
            # the history is still open, stamped into the flight ring,
            # and written as oom.json — with the ledger snapshot that
            # was live at the time — next to the ring, so `obs
            # postmortem` classifies this rank's verdict as 'oom'
            # instead of an opaque 'fatal'.
            import sys as _esys  # noqa: PLC0415

            _oom_et, _oom_ev, _ = _esys.exc_info()
            if _oom_et is not None:
                from tpu_dist.obs import memory as memory_lib  # noqa: PLC0415

                _oom = memory_lib.parse_resource_exhausted(str(_oom_ev))
                if _oom is not None:
                    counters_lib.inc("mem.oom_events")
                    _snap = self._mem_record or {"static": self._mem_static}
                    rank0_print(
                        "FATAL: device "
                        + memory_lib.oom_summary_line(_oom)
                        + " — " + memory_lib.summary_line(_snap)
                    )
                    history.log(
                        "memory", event="oom", epoch=self._last_epoch,
                        oom=_oom, ledger=_snap,
                    )
                    if self._flight is not None:
                        self._flight.record(
                            "oom", epoch=self._last_epoch,
                            requested=_oom.get("requested_bytes"),
                            used=_oom.get("used_bytes"),
                            limit=_oom.get("limit_bytes"),
                        )
                    if cfg.crash_dir:
                        from tpu_dist.obs.heartbeat import (  # noqa: PLC0415
                            per_rank_path,
                        )

                        import os as _oos  # noqa: PLC0415

                        memory_lib.write_oom_report(
                            per_rank_path(
                                _oos.path.join(
                                    cfg.crash_dir, memory_lib.OOM_NAME
                                ),
                                jax.process_index(),
                            ),
                            _oom, _snap,
                        )
            self._history = None
            history.close()
            self._heartbeat = None
            if self._flight is not None:
                # LAST teardown step so the drain/save spans above still
                # tapped the ring. Classify the exit: a propagating
                # failure stamps its fatal slot HERE (the excepthooks are
                # being unwound), preemption/interrupt stamp their own
                # terminal kind, a clean return stamps `exit` — a ring
                # that ends with none of these was a hard kill.
                import sys as _sys  # noqa: PLC0415

                spans_lib.clear_open_listener()
                self._flight.uninstall_excepthooks()
                et, ev, tb = _sys.exc_info()
                if et is None:
                    self._flight.close("exit", clean=True)
                elif issubclass(et, PreemptedError):
                    self._flight.close("preempt", epoch=self._last_epoch)
                elif issubclass(et, KeyboardInterrupt):
                    self._flight.close("interrupt", epoch=self._last_epoch)
                else:
                    self._flight.fatal(et, ev, tb)
                    self._flight.close("exit", clean=False)
                self._flight = None
                from tpu_dist.obs import flight as flight_lib  # noqa: PLC0415

                flight_lib.disarm_faulthandler(self._fault_handle)
                self._fault_handle = None

    def _emergency_save(self) -> None:
        """Ctrl-C / SIGTERM snapshot discipline (one path for both: the
        preemption handler raises PreemptedError at the step grain, so by
        the time this runs the in-flight step is finished and published).

        The ONLY source of truth is ``self._progress = (state, epoch,
        steps_done, epoch_complete)`` — published atomically at every
        position change (init/restore, each train step, epoch completion),
        so there is no interrupt window in which the pieces disagree
        (including the preamble right after a mid-epoch restore, where a
        flag-based scheme would misfile k already-trained steps as a clean
        epoch boundary).

        - Cross-process-sharded state (multi-host ZeRO-1/TP) is NOT saved:
          the gather in ckpt save is collective, and Ctrl-C lands at
          unsynchronized points per process — attempting it would deadlock
          the job. Skipped with a message instead.
        - Position "complete through epoch e": save the clean epoch-e state
          under ``e`` (kept as-is when ``ckpt_e`` already exists); nothing
          to save when no epoch has completed (e < 0).
        - Position "epoch e, k>0 steps done": EXACT snapshot under ``e``
          stamped ``mid_epoch_step=k`` (+ batch_size/seed, which pin the
          data position) — ``--resume`` continues epoch e at batch k.
        - Position "epoch e, 0 steps done" (incl. the fused epoch, which
          has no step grain): fall back to the previous clean boundary
          ``e-1`` — kept when already on disk, nothing saved when e == 0.
        """
        cfg = self.cfg
        if not cfg.ckpt_dir:
            return
        if getattr(self, "_state_poisoned", False):
            rank0_print(
                "=> interrupted while the live state was NaN-poisoned "
                "(divergence handling in flight) — emergency snapshot "
                "skipped; the last periodic checkpoint stays the newest"
            )
            return
        # drain any in-flight async write FIRST (host-local, not collective —
        # safe before the sharded-state guard): the emergency snapshot must be
        # the LAST file published, and a writer error must not abort the
        # snapshot or mask the interrupt
        self._ckpt_close(suppress=True)
        state, epoch, steps_done, complete = self._progress
        if jax.process_count() > 1 and (
            cfg.sharded_ckpt  # manifest commit needs a cross-process barrier
            or any(
                isinstance(l, jax.Array) and not l.is_fully_addressable
                for l in jax.tree_util.tree_leaves(state._asdict())
            )
        ):
            rank0_print(
                "=> interrupted; state (or the sharded-ckpt commit barrier) "
                "is cross-process — emergency snapshot skipped (collectives "
                "cannot run from a signal handler); resume from the last "
                "periodic checkpoint"
            )
            return
        io = ckpt_lib.ShardedCheckpointer if cfg.sharded_ckpt else ckpt_lib
        done_marker = (
            "ckpt_{e}.manifest.json" if cfg.sharded_ckpt else "ckpt_{e}.npz"
        )
        import os  # noqa: PLC0415

        def clean_exists(e: int) -> bool:
            return os.path.exists(
                os.path.join(cfg.ckpt_dir, done_marker.format(e=e))
            )

        def save(ckpt_epoch: int, extra_meta: dict, msg: str) -> None:
            # Donation hazard: when the interrupt lands while a train step
            # is dispatching, the published state's buffers may be (or
            # become, racing the aborted dispatch's cleanup) donated to the
            # in-flight step — serialization then raises "Array has been
            # deleted".  The save is atomic (tmp + rename), so the failed
            # attempt leaves nothing partial; skip gracefully rather than
            # crash the interrupt handler.
            try:
                io.save(cfg.ckpt_dir, state, ckpt_epoch, cfg.keep_last_ckpts,
                        extra_meta=extra_meta)
            except RuntimeError as e:
                if "deleted" not in str(e):
                    raise
                rank0_print(
                    "=> interrupted while a step held the donated state "
                    "buffers — emergency snapshot skipped; resume from the "
                    "last periodic checkpoint"
                )
                return
            rank0_print(msg)

        if complete:
            if epoch < 0:
                return  # nothing trained yet
            if clean_exists(epoch):
                rank0_print(
                    f"=> interrupted after epoch {epoch} completed; clean "
                    f"ckpt_{epoch} already on disk — kept as-is"
                )
                return
            save(epoch, self._ckpt_meta(),
                 f"=> interrupted after epoch {epoch} completed; "
                 f"saved as epoch {epoch}")
            return
        if steps_done > 0:
            # Exact mid-epoch snapshot: state after steps_done steps of
            # epoch, stamped with the step offset plus the two config
            # values the data position depends on (the epoch-seeded
            # permutation makes (seed, epoch, batch_size, step) pin it
            # exactly); _restore_latest refuses a mismatched resume.
            save(epoch,
                 {**self._ckpt_meta(),
                  **self._mid_epoch_position(int(steps_done))},
                 f"=> interrupted mid-epoch {epoch} after step "
                 f"{steps_done - 1}; exact snapshot saved — resume continues "
                 f"epoch {epoch} at step {steps_done}")
            return
        if epoch <= 0:
            return
        prev = epoch - 1
        if clean_exists(prev):
            rank0_print(
                f"=> interrupted mid-epoch {epoch}; clean ckpt_{prev} "
                f"already on disk — kept as-is, resume re-runs epoch {epoch}"
            )
            return
        save(prev, self._ckpt_meta(),
             f"=> interrupted mid-epoch {epoch}; state saved to "
             f"{cfg.ckpt_dir} as epoch {prev} — resume re-runs epoch "
             f"{epoch}")

    def _close_goodput(self, history) -> None:
        """Run-end ledger bookkeeping: fold the tail window (final save,
        drain, teardown preamble), write the ``final`` totals record, and
        print the rank-0 ledger line. Best-effort like the rest of the
        telemetry teardown — the books must never mask a propagating
        training error."""
        try:
            tail = self._goodput.window_record()
            totals = self._goodput.run_totals()
            if history.path:
                # tail=True distinguishes this teardown window from the
                # per-epoch window logged under the same epoch number
                history.log("goodput", epoch=self._last_epoch, tail=True,
                            **tail)
                history.log("goodput", final=True, **totals)
            if history.path or self.cfg.trace_file:
                rank0_print("=> " + goodput_lib.ledger_line(totals))
        except OSError as e:
            rank0_print(f"WARNING: goodput ledger close failed: {e}")

    def _export_telemetry(self, history) -> None:
        """End-of-run span disposal (rank 0 — fit() arms telemetry there
        only): drain the tail into the JSONL history, write --trace_file,
        disarm the recorder. Best-effort: telemetry must never mask a
        propagating training error."""
        cfg = self.cfg
        try:
            # one drain path (history record + capped accumulator with
            # counted drops) for the tail too — no silent overflow here
            self._drain_spans(history, self._last_epoch)
            if cfg.trace_file:
                spans_lib.export_chrome_trace(
                    cfg.trace_file, extra_events=self._trace_events
                )
                rank0_print(
                    f"=> wrote host-span Chrome trace to {cfg.trace_file} "
                    f"({len(self._trace_events)} events; load in Perfetto)"
                )
        except OSError as e:
            rank0_print(f"WARNING: telemetry export failed: {e}")
        finally:
            spans_lib.disable()
            self._trace_events = []

    def _drain_spans(self, history, epoch: int) -> None:
        """Move this epoch's host spans out of the in-memory buffer: into
        the JSONL history (a ``spans`` record, streamed to disk) and/or
        the --trace_file accumulator, which is capped at the same
        MAX_EVENTS budget as the live buffer — a week-long run keeps its
        earliest events and counts the overflow, never grows unbounded."""
        if not spans_lib.enabled():
            return
        ev = spans_lib.drain()
        if not ev:
            return
        if self.cfg.log_file:
            history.log("spans", epoch=epoch, events=ev)
        if self.cfg.trace_file:
            room = spans_lib.MAX_EVENTS - len(self._trace_events)
            if room > 0:
                self._trace_events.extend(ev[:room])
            if len(ev) > max(room, 0):
                counters_lib.inc(
                    "spans.trace_export_dropped", len(ev) - max(room, 0)
                )

    def _fit_loop(self, epochs: int, history, last: dict) -> dict:
        cfg = self.cfg
        for epoch in range(self.start_epoch, epochs):
            self._last_epoch = epoch
            self._in_epoch = True
            # a restored mid-epoch snapshot applies to its own epoch only.
            # _progress stays whatever was last published (the restore point
            # or the previous epoch's completion) until train_epoch's own
            # publish — every interrupt window reads a consistent position.
            start_step, self._resume_step = self._resume_step, 0
            start_examples, self._resume_examples = self._resume_examples, 0
            # the epoch-0 blanket trace only when triggered/manual capture
            # does NOT own --profile_dir (two live jax.profiler traces
            # cannot nest)
            if (
                cfg.profile_dir and epoch == self.start_epoch
                and self._profiler is None
            ):
                from tpu_dist.obs.profile import (  # noqa: PLC0415
                    analyze_capture_quietly,
                    trace,
                )

                with trace(cfg.profile_dir):
                    last = self.train_epoch(
                        epoch, start_step=start_step,
                        start_examples=start_examples,
                    )
                if mesh_lib.is_primary():
                    # the blanket capture gets the same read-back as a
                    # triggered one: attribution record + summary line +
                    # calibration gauges (obs/xprof.py)
                    analysis, a_err = analyze_capture_quietly(cfg.profile_dir)
                    self._note_capture_analysis(
                        analysis, a_err, epoch=epoch, reason="profile_dir",
                        capture_dir=cfg.profile_dir,
                        steps=last.get("steps"),
                    )
            else:
                last = self.train_epoch(
                    epoch, start_step=start_step,
                    start_examples=start_examples,
                )
            self._in_epoch = False
            # epoch fully trained: one atomic publish flips the position to
            # "complete through epoch" for the eval/save window below
            self._progress = (self.state, epoch, 0, True)
            history.log("train_epoch", epoch=epoch, **last)
            self._drain_spans(history, epoch)
            if cfg.straggler_threshold > 0:
                # COLLECTIVE (allgather of two floats per process): every
                # process reaches this once per epoch — same contract as
                # the restore ladder's agreement check
                from tpu_dist.obs import straggler as straggler_lib  # noqa: PLC0415

                srec = straggler_lib.epoch_skew(
                    float(last.get("epoch_time", 0.0)),
                    float(last.get("data_stall_frac", 0.0)),
                    epoch=epoch, threshold=cfg.straggler_threshold,
                )
                if srec["straggler"]:
                    history.log("straggler", epoch=epoch, **srec)
                    if (
                        self._profiler is not None
                        and "straggler" in self._profile_triggers
                        and mesh_lib.process_index() == srec["worst_rank"]
                    ):
                        # the FLAGGED host arms: its next-epoch steps are
                        # the timeline that explains the skew (rank 0's
                        # would just show it waiting at the collective)
                        self._profiler.arm("straggler")
            if self._tb is not None:
                for k in ("loss", "acc1", "acc5", "images_per_sec", "mfu"):
                    if k in last:
                        self._tb.add_scalar(f"train/{k}", last[k], epoch)
                self._tb.add_scalar("train/lr", self._lr(epoch), epoch)
            if cfg.eval_every and (epoch + 1) % cfg.eval_every == 0:
                with self._goodput.timed("eval"):
                    if self._fused_runner is not None:
                        t_ev = time.perf_counter()
                        sums = _fetch_metrics(
                            self._fused_eval(self.state, *self._fused_test_data)
                        )
                        spans_lib.add_event(
                            "eval/fused", t_ev, time.perf_counter() - t_ev,
                            epoch=epoch,
                        )
                        n = max(sums["count"], 1.0)
                        t1 = sums["top1"] / n * 100.0
                        t5 = sums["top5"] / n * 100.0
                        vloss = sums["loss"] / n
                        rank0_print(f" * Acc@1 {t1:.3f} Acc@5 {t5:.3f} (epoch {epoch}, fused)")
                    else:
                        t1, t5, vloss = validate(
                            self.test_loader, self.state, self.eval_step, epoch=epoch
                        )
                last.update(val_top1=t1, val_top5=t5, val_loss=vloss)
                history.log("eval", epoch=epoch, top1=t1, top5=t5, loss=vloss)
                if self._tb is not None:
                    self._tb.add_scalar("eval/top1", t1, epoch)
                    self._tb.add_scalar("eval/top5", t5, epoch)
                    self._tb.add_scalar("eval/loss", vloss, epoch)
                if cfg.ckpt_dir and t1 > self._best_top1:
                    self._best_top1 = t1
                    with self._goodput.timed("ckpt"):
                        self._ckpt_io().save_best(
                            cfg.ckpt_dir, self.state, epoch, t1,
                            extra_meta=self._ckpt_meta(),
                        )
            if cfg.ckpt_dir and (
                (epoch + 1) % cfg.save_every == 0
                # with periodic mid-epoch snapshots on, EVERY epoch end
                # writes the clean checkpoint — otherwise a stale
                # mid-epoch ckpt_e would stay newest across the boundary
                # and the "at most N steps lost" guarantee breaks
                or cfg.mid_epoch_save_every > 0
            ):
                with self._goodput.timed("ckpt"):
                    self._ckpt_io().save(
                        cfg.ckpt_dir, self.state, epoch, cfg.keep_last_ckpts,
                        extra_meta=self._ckpt_meta(),
                    )
            # close this epoch's goodput window (train + eval + save):
            # one v4 record per epoch; the records chain, partitioning the
            # run's wall-clock exactly (obs/goodput.py)
            live = self._exporter is not None or self._alerts is not None
            if history.path or live:
                # the live layer needs the window CLOSED too (run totals
                # feed the goodput gauges and the goodput-floor rule)
                gp_rec = self._goodput.window_record()
                if history.path:
                    history.log("goodput", epoch=epoch, **gp_rec)
            if live:
                self._epoch_live_update(epoch, last)
            if preemption.requested():
                # SIGTERM during eval/save lands here: the epoch is complete
                # and published — the emergency path keeps/writes ckpt_epoch
                raise PreemptedError(
                    f"SIGTERM observed after epoch {epoch} completed — "
                    f"shutting down at the epoch boundary"
                )
        if cfg.ckpt_dir:
            with self._goodput.timed("ckpt"):
                self._ckpt_io().save(
                    cfg.ckpt_dir, self.state, epochs - 1, cfg.keep_last_ckpts,
                    extra_meta=self._ckpt_meta(),
                )
        return last  # fit() drains the async writer before returning
