"""Compiled train/eval steps over the device mesh.

This module is where the reference's four native engines collapse into one
TPU program (SURVEY §7 design stance):

* **DDP gradient allreduce** (``reducer.cpp`` behind ``distributed.py:60``)
  → ``lax.pmean(grads, 'data')`` inside the step; XLA's latency-hiding
  scheduler overlaps the collective with the backward, which is exactly the
  bucketed-overlap service the DDP reducer provides.
* **DataParallel scatter/replicate/gather** (``dataparallel.py:47``)
  → the batch arrives sharded on the ``data`` axis, params arrive
  replicated; nothing to scatter.
* **apex AMP** (``distributed_apex.py:86,119-120``) → a bf16 compute policy:
  master params stay f32, the forward/backward runs in bf16. TPUs have
  hardware bf16 with f32 accumulation in the MXU, so there is NO loss
  scaling — the reason apex needs it (fp16 underflow) does not exist here.
* **grad accumulation + no_sync** (``distributed_gradient_accumulation.py:
  90-111``) → a ``lax.scan`` over sub-batches accumulating LOCAL grads, with
  the single ``pmean`` after the scan. Suppressing cross-rank traffic on
  non-boundary sub-steps is precisely torch's ``model.no_sync()`` (``:106``);
  the 1/K loss scaling (``:103,110``) appears here as the mean over chunk
  grads.
* **per-step barrier + reduce_mean of metrics** (``distributed.py:95,109``)
  → the metric ``pmean`` rides the same compiled step; the barrier is
  deleted (XLA dataflow already orders collectives — SURVEY §5).

Everything is wrapped in ``jax.jit`` over a ``shard_map``, so one Python
call runs the whole step on every chip with static shapes and no host sync.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.comm.compat import shard_map
from tpu_dist.comm.quantize import DEFAULT_CHUNK
from tpu_dist.nn import functional as F
from tpu_dist.obs import hlo_scopes
from tpu_dist.train.state import TrainState


def extract_aux_loss(new_bn):
    """Split a model's auxiliary training loss out of its returned state.

    MoE models report the router load-balancing loss by returning
    ``{"moe_aux_loss": scalar}`` in the state dict during training
    (``vit_moe.py``); it must be POPPED before the state is stored so the
    TrainState pytree structure stays identical step to step (and matches
    the eval-time state). Returns ``(clean_state, aux_or_None)``."""
    if isinstance(new_bn, dict) and "moe_aux_loss" in new_bn:
        new_bn = dict(new_bn)
        return new_bn, new_bn.pop("moe_aux_loss")
    return new_bn, None


GRAD_COMPRESSION_MODES = ("none", "bf16", "int8", "int8_ef")

# ONE registry of the shard-auditable parallelism config families: name →
# the :func:`make_train_step` kwargs that select the family. This is the
# enumeration the static analyzers walk (the jaxpr audit's budget cases,
# the shardlint HLO audit — tpu_dist/analysis): every entry lowers to a
# distinct collective inventory, and each gets its own verified entry in
# ``shard_report.json`` (docs/shard_report.md). Families that need a
# model/mesh beyond the flag combo (fsdp's per-leaf specs, tp's
# param_specs, sp's ring-attention model) carry the axis flags here and
# get their builders in ``analysis/shardlint.py``.
SHARD_CONFIG_FAMILIES: dict = {
    "dp_sgd": {},
    "dp_sgd_accum4": {"grad_accum_steps": 4},
    "dp_bf16": {"compute_dtype": "bfloat16"},  # compute policy, f32 wire
    "dp_wire_bf16": {"grad_compression": "bf16"},
    "dp_int8": {"grad_compression": "int8"},
    "dp_int8_ef": {"grad_compression": "int8_ef"},
    "zero1_sgd": {"shard_weight_update": True},
    "zero1_int8": {"shard_weight_update": True, "grad_compression": "int8"},
    "dp_device_metrics": {"device_metrics": True},
    "tp": {"tp_axis": "model"},    # + param_specs from the model
    "sp": {"seq_axis": "seq"},     # + a ring-attention model
    "fsdp": {},                    # the GSPMD engine (parallel/fsdp.py)
}


def family_step_kwargs(name: str) -> dict:
    """Resolve a :data:`SHARD_CONFIG_FAMILIES` entry to real
    :func:`make_train_step` kwargs (the registry stores dtypes by NAME so
    it stays a plain-data enumeration)."""
    kw = dict(SHARD_CONFIG_FAMILIES[name])
    if isinstance(kw.get("compute_dtype"), str):
        kw["compute_dtype"] = jnp.dtype(kw["compute_dtype"]).type
    return kw

# Modes that use the quantized two-stage reduce below. They are scoped to
# the plain data-parallel reduce (per-step and fused-epoch) and the ZeRO-1
# reduce-scatter; the model-parallel reduces (tp/ep/pp/sp) keep the cast
# wire formats — see make_train_step's composition wall.
QUANTIZED_MODES = ("int8", "int8_ef")

_QUANT_KEY_SEED = 0x1D8  # stochastic-rounding PRNG stream, folded per step


def validate_grad_compression(mode: str) -> None:
    if mode not in GRAD_COMPRESSION_MODES:
        raise ValueError(
            f"grad_compression must be one of {GRAD_COMPRESSION_MODES}, "
            f"got {mode!r}"
        )


def grad_wire(g, mode: str):
    """Gradient wire format for cross-replica reduces — ONE definition of
    the compression contract, shared by the per-step path here and the
    fused-epoch path (``train/epoch.py``) so the semantics cannot drift.
    ``'bf16'`` halves gradient ICI/DCN traffic (full f32 exponent range,
    so the pre-reduce 1/n scaling cannot underflow). The int8 modes do not
    go through this per-leaf cast — they reduce on the flat quantized
    two-stage path (:func:`quantized_pmean_flat`)."""
    return g.astype(jnp.bfloat16) if mode == "bf16" else g


def grad_unwire(g, like, mode: str):
    """Restore the update dtype after a compressed reduce."""
    return g.astype(like.dtype) if mode == "bf16" else g


def ef_state_spec(mode: str, *, zero1: bool = False, axis: str = mesh_lib.DATA_AXIS):
    """PartitionSpec tree for ``TrainState.ef`` under ``mode``.

    The residuals are flat f32 vectors laid over the data axis (per-replica
    state — each replica compensates ITS OWN quantization error): ``r1``
    covers the leg-1 (send-side) error over the full padded gradient,
    ``r2`` the leg-2 error on the owned reduced shard. ZeRO-1 has no
    quantized second leg (the param all-gather stays in the param dtype),
    so only ``r1`` exists there. Every other mode carries ``()``.
    """
    if mode != "int8_ef":
        return ()
    spec = {"r1": P(axis)}
    if not zero1:
        spec["r2"] = P(axis)
    return spec


def ef_state_host_zeros(params, n: int, *, zero1: bool = False):
    """Host (numpy) zero residuals matching :func:`ef_state_spec`'s layout
    for an ``n``-way data axis — the placement-free half of
    :func:`init_ef_state` (the Trainer places these with
    ``mesh.place_host_tree``, which also covers multi-host meshes)."""
    import numpy as np  # noqa: PLC0415
    from jax.flatten_util import ravel_pytree  # noqa: PLC0415

    from tpu_dist.comm.quantize import padded_len  # noqa: PLC0415

    L = ravel_pytree(params)[0].shape[0]
    P_len = padded_len(L, n)
    ef = {"r1": np.zeros((n * P_len,), np.float32)}
    if not zero1:
        ef["r2"] = np.zeros((P_len,), np.float32)
    return ef


def init_ef_state(
    params, mesh: Mesh, *, zero1: bool = False, axis: str = mesh_lib.DATA_AXIS,
):
    """Zero error-feedback residuals, placed on the mesh (the ``int8_ef``
    counterpart of :func:`init_sharded_opt_state`): ``r1`` is one padded
    gradient-length vector PER replica (global ``(n*P,)``, sharded over
    ``axis``), ``r2`` one reduced-shard vector per replica (global
    ``(P,)``)."""
    ef = ef_state_host_zeros(params, int(mesh.shape[axis]), zero1=zero1)
    return mesh_lib.place_host_tree(
        mesh, ef, ef_state_spec("int8_ef", zero1=zero1, axis=axis)
    )


def _quantized_reduce_scatter_rows(rows, axis: str, key, chunk: int):
    """EQuARX-style quantized reduce-scatter of ``rows`` ``(n, m)`` over
    ``axis``: quantize → int8 ``all_to_all`` (+ tiny f32 scale sideband) →
    local dequantize-sum. Returns ``(reduced_shard (m,), sent)`` where
    ``sent`` is this replica's dequantized transmission (for the
    error-feedback residual).

    This is the software spelling of a quantized ``psum_scatter``: the
    transpose leg carries int8 instead of f32 (4× fewer wire bytes), the
    reduction itself runs locally in f32 — no int overflow, same
    schedule-shape as the ring reduce-scatter XLA emits for ``psum``.
    """
    from tpu_dist.comm.quantize import dequantize_int8, quantize_int8  # noqa: PLC0415

    q, s = quantize_int8(rows, chunk, key)
    qt = lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=True)
    st = lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=True)
    reduced = jnp.sum(dequantize_int8(qt, st, chunk), axis=0)
    return reduced, dequantize_int8(q, s, chunk)


def quantized_pmean_flat(grads, axis: str, *, key, ef, chunk: int):
    """Two-stage quantized mean of a grad pytree over ``axis`` — the int8
    replacement for ``lax.pmean(grads)`` (EQuARX, arXiv:2506.17615): BOTH
    wire legs are compressed, not just the input.

    1. Flatten + pad to a multiple of n, pre-scale by 1/n (so the
       dequantize-sum lands on the MEAN; bf16-wire precedent: the f32
       exponent range of the scales makes this safe).
    2. Leg 1: per-chunk int8 quantize, ``all_to_all`` the rows — each
       replica reduces its own shard locally in f32 (quantized
       reduce-scatter).
    3. Leg 2: re-quantize the reduced shard, int8 ``all_gather`` (+ scale
       sideband), dequantize, unravel.

    ``ef``: ``()`` for plain ``int8`` (stochastic rounding alone keeps the
    estimate unbiased); the ``{"r1", "r2"}`` residual dict for
    ``int8_ef`` — the residual is added BEFORE quantization and the
    realized error carried to the next step (error feedback, per replica,
    for each leg independently). Returns ``(mean_grads, new_ef)``.
    """
    from jax.flatten_util import ravel_pytree  # noqa: PLC0415

    from tpu_dist.comm.quantize import (  # noqa: PLC0415
        dequantize_int8,
        padded_len,
        quantize_int8,
    )

    n = lax.axis_size(axis)
    flat, unravel = ravel_pytree(grads)
    L = flat.shape[0]
    P_len = padded_len(L, n)
    m = P_len // n
    x = jnp.pad(flat, (0, P_len - L)) / n
    if ef:
        x = x + ef["r1"]
    k1 = jax.random.fold_in(key, 1)
    k2 = jax.random.fold_in(key, 2)
    reduced, sent = _quantized_reduce_scatter_rows(
        x.reshape(n, m), axis, k1, chunk
    )
    new_ef = ()
    if ef:
        new_ef = {"r1": x - sent.reshape(P_len)}
        reduced = reduced + ef["r2"]
    q2, s2 = quantize_int8(reduced, chunk, k2)
    if ef:
        new_ef["r2"] = reduced - dequantize_int8(q2, s2, chunk)
    qg = lax.all_gather(q2, axis, tiled=True)
    sg = lax.all_gather(s2, axis, tiled=True)
    full = dequantize_int8(
        qg.reshape(n, m), sg.reshape(n, -1), chunk
    ).reshape(P_len)[:L]
    return unravel(full), new_ef


def compressed_pmean(grads, axes, mode: str, *, key=None, ef=()):
    """Cross-replica grad mean on the compressed wire format — the shared
    entry point of the per-step and fused-epoch paths. Returns
    ``(mean_grads, new_ef)``; ``new_ef`` is ``()`` except under
    ``int8_ef`` (pass the state's residuals in as ``ef``). ``key`` seeds
    the stochastic rounding for the quantized modes (required there)."""
    if mode in QUANTIZED_MODES:
        if isinstance(axes, (tuple, list)):
            raise ValueError(
                "int8 grad compression reduces over a single mesh axis "
                f"(got {axes!r}) — see make_train_step's composition wall"
            )
        return quantized_pmean_flat(
            grads, axes, key=key, ef=ef if mode == "int8_ef" else (),
            chunk=DEFAULT_CHUNK,
        )
    if mode == "none":
        return lax.pmean(grads, axes), ef
    # one multi-operand psum for the whole tree (same eqn shape as the
    # per-step path, so the TD101 budgets match across both consumers)
    wired = lax.pmean(
        jax.tree_util.tree_map(lambda g: grad_wire(g, mode), grads), axes
    )
    return jax.tree_util.tree_map(
        lambda g, like: grad_unwire(g, like, mode), wired, grads
    ), ef


def make_train_step(
    model_apply: Callable,
    optimizer,
    mesh: Mesh,
    *,
    grad_accum_steps: int = 1,
    sync_bn: bool = True,
    compute_dtype=jnp.float32,
    axis: str = mesh_lib.DATA_AXIS,
    donate: bool = True,
    shard_weight_update: bool = False,
    label_smoothing: float = 0.0,
    grad_clip_norm: float = 0.0,
    moe_aux_coef: float = 0.01,
    seq_axis: str | None = None,
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    pp_axis: str | None = None,
    param_specs=None,
    remat: bool = False,
    grad_compression: str = "none",
    device_metrics: bool = False,
    model_kwargs: dict | None = None,
    model_loss: Callable | None = None,
):
    """Build ``step(state, images, labels, lr) -> (state, metrics)``.

    ``model_loss``: a model that computes its own loss (a token model:
    ``nn/nemotron_h.py``) gives ``model_loss(params, state, inputs, targets,
    train=, compute_dtype=) -> (loss, new_state, stats)`` in place of
    ``model_apply`` + cross-entropy. Inputs are integer ids and stay so;
    targets are one a position; the parameters reach the model in float32
    and it casts what it multiplies (its router and recurrences stay
    float32); recomputation is the model's own, a layer at a time. The
    state it returns is carried as ``bn_state`` is. ``stats`` holds the
    sums the metrics are made of (``top1``, ``top5``, ``weight_sum``) and
    any counts of the model's own, which ride the metrics dict (one fetch).

    ``model_apply(params, bn_state, x, train=, axis_name=)`` is the
    functional model (e.g. ``ResNetDef.apply``). ``metrics`` is a dict of
    replica-averaged scalars: loss, top-1/top-5 accuracy (the reference's
    per-step ``reduce_mean(loss)`` + ``accuracy`` line,
    ``distributed.py:104-111``).

    ``shard_weight_update=True`` enables cross-replica weight-update
    sharding (Xu et al. 2020, arXiv:2004.13336 — ZeRO-1 on TPU): the grad
    allreduce becomes reduce-scatter, each replica updates only its 1/n
    shard of the (flattened) parameters with a SHARDED momentum state, and
    an all-gather rebuilds the replicated params. Same numerics, 1/n the
    optimizer-state memory, and 2x less collective traffic than
    allreduce+full-update at large scale. The optimizer state becomes one
    flat f32 array per replica — build it with
    :func:`init_sharded_opt_state`.

    ``seq_axis``: sequence-parallel training over a 2-D mesh (DP×SP). The
    batch stays sharded on ``axis`` and replicated over ``seq_axis``; the
    model (e.g. ViT) slices its own token chunk and runs ring attention
    over the axis. Parameter gradients are ``pmean``-ed over ``seq_axis``
    on top of the ``pmean`` over the data axis (each shard differentiates a
    full loss replica). Composes with ``shard_weight_update`` (the seq
    pmean happens before the data-axis reduce-scatter).

    ``grad_compression='bf16'``: cast gradients to bf16 for the
    cross-replica reduce and back to f32 for the update — halves gradient
    ICI/DCN traffic, the TPU equivalent of torch DDP's
    ``bf16_compress_hook`` communication hook (quantized-allreduce family,
    cf. EQuARX, arXiv:2506.17615). Local accumulation (grad_accum scan)
    stays f32; only the wire format changes. Applies to the DP/EP/SP
    reduces and the ZeRO-1 reduce-scatter; the FSDP engine's collectives
    are GSPMD-inserted and are not hooked.

    ``grad_compression='int8'`` / ``'int8_ef'``: per-chunk scaled int8
    with stochastic rounding, reduced as a two-stage quantized
    reduce-scatter + all-gather (EQuARX-style — BOTH wire legs are int8,
    ~4× less gradient traffic than f32, 2× less than bf16; see
    docs/compression.md). ``int8_ef`` adds per-replica error-feedback
    residuals carried in ``TrainState.ef`` (build with
    :func:`init_ef_state`), so the realized quantization error is
    compensated on the next step rather than discarded. Scoped to the
    plain data-parallel reduce and the ZeRO-1 reduce-scatter (the ZeRO-1
    param all-gather stays in the param dtype — it carries weights, not
    gradients); the model-parallel reduces (tp/ep/pp/sp) are refused, and
    the FSDP engine's GSPMD collectives remain unhookable.

    ``device_metrics=True``: fuse the training-health scalars
    (``obs/device_stats.py`` — global grad norm, param norm, update
    ratio, nonfinite-leaf count) into the step's metrics dict. Computed
    on the POST-reduce gradients, so everything is local arithmetic:
    zero extra collectives, zero extra host fetches (the scalars ride the
    metrics tree the trainer already fetches once per logged step) — the
    TD107 jaxpr rule pins both halves, and flag-off is byte-identical.
    Scoped to the replicated-param paths (plain DP/SP, any
    ``grad_compression``, grad accumulation): under ZeRO-1/tp/ep/pp the
    reduced gradient exists only as shards, so the global norms would
    need extra collectives — refused rather than silently costed.
    """
    K = int(grad_accum_steps)
    n_axis = int(mesh.shape[axis])
    validate_grad_compression(grad_compression)
    quantized = grad_compression in QUANTIZED_MODES
    if quantized and any(
        a is not None for a in (tp_axis, ep_axis, pp_axis, seq_axis)
    ):
        # the flat two-stage reduce assumes a replicated param tree and one
        # reduce axis; the model-parallel engines reduce per leaf over
        # other axes with their own layouts — cast compression (bf16)
        # composes there, the quantized transpose does not
        raise ValueError(
            f"grad_compression={grad_compression!r} is scoped to the plain "
            "data-parallel and ZeRO-1 paths; it cannot combine with "
            "sp/tp/ep/pp (use grad_compression='bf16' there)"
        )
    if device_metrics and (
        shard_weight_update
        or any(a is not None for a in (tp_axis, ep_axis, pp_axis))
    ):
        # the health scalars are free only where the reduced grad tree and
        # the params are replica-identical; under ZeRO-1/tp/ep/pp they
        # exist as shards and the global norms would need collectives the
        # TD107 zero-cost contract forbids
        raise ValueError(
            "device_metrics is scoped to the replicated-param paths "
            "(plain DP/SP, any grad_compression) — it cannot combine "
            "with shard_weight_update/tp/ep/pp"
        )
    if device_metrics:
        from tpu_dist.obs.device_stats import compute_device_stats  # noqa: PLC0415

    def wire(g):
        return grad_wire(g, grad_compression)

    def unwire(g, like):
        return grad_unwire(g, like, grad_compression)

    def quant_key(step):
        """Per-step, per-replica stochastic-rounding stream (deterministic
        replay: folds the step counter, then this replica's position)."""
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(_QUANT_KEY_SEED), step),
            lax.axis_index(axis),
        )
    # Composition walls. grad_clip_norm composes with EVERY axis (the clip
    # computes a shard-aware global norm — see clip_grads). The remaining
    # exclusions are genuinely structural, not deferred work:
    if tp_axis is not None:
        if param_specs is None:
            raise ValueError("tp_axis requires param_specs (per-leaf shardings)")
        if shard_weight_update:
            # ZeRO-1 is BY DESIGN the data-parallel fast path (SGD or
            # AdamW): it ravels the (replicated) param tree into flat
            # vectors and reduce-scatters over the data axis. Under TP the
            # local tree is a per-shard slice, so the flat layout no longer
            # lines up — and rather than grow a second sharding engine,
            # that territory belongs to FSDP (parallel/fsdp.py), which
            # shards per-leaf via GSPMD and composes by specs. Final
            # scoping decision, not deferred work (VERDICT r2 #6).
            raise ValueError(
                "tp_axis + shard_weight_update is out of ZeRO-1's scope "
                "(DP-only fast path by design) — use --fsdp for "
                "sharded weight updates beyond plain DP"
            )
        # tp_axis + seq_axis composes (3-D DPxTPxSP): the conjugate VJP ops
        # absorb the model axis, grads pmean over data+seq — verified exact
        # (tests/test_3d_mesh_training.py)
    if ep_axis is not None:
        if param_specs is None:
            raise ValueError("ep_axis requires param_specs (per-leaf shardings)")
        if shard_weight_update or seq_axis or tp_axis:
            # ZeRO-1: same flat-layout conflict as under TP. seq/tp: the MoE
            # model's dispatch all_to_all and the ring-attention / Megatron
            # sharding would have to thread the same token dimension through
            # two conflicting layouts — a model-architecture change, not a
            # step-function flag.
            raise ValueError(
                "ep_axis is incompatible with shard_weight_update / "
                "seq_axis / tp_axis (structural; see docstring)"
            )
    if pp_axis is not None:
        if param_specs is None:
            raise ValueError("pp_axis requires param_specs (per-leaf shardings)")
        if shard_weight_update or seq_axis or ep_axis:
            # ZeRO-1: flat-layout conflict (stage-sharded leaves). seq/ep
            # inside a pipeline stage would thread the token dim through two
            # conflicting layouts (ring/all_to_all under the stage ring).
            # tp COMPOSES (Megatron PP×TP): the per-block psum pair runs
            # over the model axis inside each stage, orthogonal to the pipe
            # ring's ppermute — tests/test_pp_tp_training.py pins it.
            raise ValueError(
                "pp_axis is incompatible with shard_weight_update / "
                "seq_axis / ep_axis (structural; see docstring)"
            )
    # the expert axis doubles as a data axis outside the MoE: batch shards
    # over both, metrics/loss reduce over both
    batch_axes = (axis, ep_axis) if ep_axis is not None else axis
    bn_axis = batch_axes if sync_bn else None

    def token_loss_fn(params, bn_state, tokens, targets):
        loss, new_bn, stats = model_loss(
            params, bn_state, tokens, targets, train=True,
            compute_dtype=compute_dtype, axis_name=batch_axes, **(model_kwargs or {}),
        )
        return loss, (new_bn, stats)

    def loss_fn(params, bn_state, images, labels):
        x = images.astype(compute_dtype)
        p = jax.tree_util.tree_map(lambda t: t.astype(compute_dtype), params)
        kw = {}
        if seq_axis is not None:
            kw["seq_axis"] = seq_axis
        if tp_axis is not None:
            kw["tp_axis"] = tp_axis
        if ep_axis is not None:
            kw["ep_axis"] = ep_axis
        if pp_axis is not None:
            kw["pp_axis"] = pp_axis
        if model_kwargs:
            kw.update(model_kwargs)
        logits, new_bn = model_apply(p, bn_state, x, train=True, axis_name=bn_axis, **kw)
        new_bn, aux = extract_aux_loss(new_bn)
        loss = F.cross_entropy(logits, labels, label_smoothing=label_smoothing)
        if aux is not None:
            loss = loss + moe_aux_coef * aux.astype(loss.dtype)
        return loss, (new_bn, logits)

    def clip_grads(grads):
        """Global-norm clip on the ALREADY-REDUCED grads (so the norm is the
        true global-batch gradient norm, identical on every replica).

        Under model parallelism (tp/ep/pp) some leaves are SHARDED across a
        model axis — their local sum-of-squares is only this shard's slice of
        the leaf's norm. Leaves are grouped by the model axes in their spec:
        each sharded group's sum gets one ``psum`` over those axes
        (shard-norm pattern, same as the ZeRO-1 path below); replicated
        leaves' grads are identical on every model shard (the model's VJP
        collectives guarantee it) and contribute locally. A final ``pmean``
        keeps the scale bit-identical on every shard."""
        if grad_clip_norm <= 0.0:
            return grads
        model_axes = tuple(a for a in (tp_axis, ep_axis, pp_axis) if a is not None)
        if not model_axes or param_specs is None:
            sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads))
        else:
            def leaf_model_axes(spec):
                names = set()
                for entry in spec:
                    for name in (entry if isinstance(entry, tuple) else (entry,)):
                        if name is not None:
                            names.add(name)
                return tuple(a for a in model_axes if a in names)

            groups: dict = {}

            def accumulate(g, spec):
                groups.setdefault(leaf_model_axes(spec), []).append(
                    jnp.sum(jnp.square(g))
                )
                return g

            jax.tree_util.tree_map(accumulate, grads, param_specs)
            sq = 0.0
            for axes, sums in groups.items():
                group_sq = sum(sums)
                if axes:
                    group_sq = lax.psum(group_sq, axes)
                sq = sq + group_sq
            sq = lax.pmean(sq, model_axes)
        norm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, grad_clip_norm / jnp.maximum(norm, 1e-12))
        return jax.tree_util.tree_map(lambda g: g * scale, grads)

    if model_loss is not None:
        if K > 1 or shard_weight_update or quantized or any(
            a is not None for a in (tp_axis, ep_axis, pp_axis, seq_axis)
        ):
            raise ValueError(
                "a model with its own loss (token models) runs the plain "
                "data-parallel step: no grad accumulation, ZeRO-1, int8 wire "
                "or tp/ep/pp/sp yet"
            )
        loss_fn = token_loss_fn
    elif remat:
        # rematerialize the forward during the backward: activations are
        # recomputed instead of stored, trading ~33% extra FLOPs for O(depth)
        # less activation memory — the standard TPU lever for bigger batches.
        # Numerics identical (tests/test_remat.py).
        loss_fn = jax.checkpoint(loss_fn)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def local_grads(params, bn_state, images, labels):
        """Local (pre-allreduce) grads; grad-accum via scan when K > 1."""
        if K == 1:
            (loss, (bn, logits)), grads = grad_fn(params, bn_state, images, labels)
            return loss, grads, bn, logits
        # [B, ...] -> [K, B/K, ...]; BN state threads through the scan so
        # running stats update every sub-step, like torch.
        chunked = jax.tree_util.tree_map(
            lambda t: t.reshape((K, t.shape[0] // K) + t.shape[1:]), (images, labels)
        )

        def body(carry, chunk):
            bn, acc = carry
            imgs, lbls = chunk
            (loss, (bn, logits)), g = grad_fn(params, bn, imgs, lbls)
            acc = jax.tree_util.tree_map(jnp.add, acc, g)
            return (bn, acc), (loss, logits)

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        (bn, acc), (losses, logits) = lax.scan(body, (bn_state, zero), chunked)
        grads = jax.tree_util.tree_map(lambda g: g / K, acc)  # tutorials/1 mean math
        logits = logits.reshape((-1,) + logits.shape[2:])
        return losses.mean(), grads, bn, logits

    def step_local(state: TrainState, images, labels, lr):
        with hlo_scopes.scope("step/loss_grad"):
            loss, grads, new_bn, logits = local_grads(
                state.params, state.bn_state, images, labels)

        if not sync_bn:
            # Local-BN replicas hold diverged running stats; average them so
            # the replicated state stays consistent (torch instead keeps
            # per-rank stats and saves rank 0's — documented deviation).
            new_bn = lax.pmean(new_bn, axis)

        new_ef = state.ef
        if shard_weight_update:
            new_params, new_opt, new_ef = _sharded_update(state, grads, lr)
        else:
            with hlo_scopes.scope("step/grad_reduce"):
                grads, new_ef = _replicated_grad_reduce(state, grads)
            with hlo_scopes.scope("step/optimizer"):
                grads = clip_grads(grads)
                new_params, new_opt = optimizer.update(
                    grads, state.opt_state, state.params, lr
                )
        new_state = TrainState(new_params, new_bn, new_opt, state.step + 1, new_ef)

        with hlo_scopes.scope("step/metrics"):
            metrics = _metrics(state, loss, logits, labels, grads, new_params)
        return new_state, metrics

    def _replicated_grad_reduce(state: TrainState, grads):
        """The cross-replica mean of a replicated parameter tree's gradients;
        ``(grads, new_ef)``."""
        if ep_axis is not None:
            return _ep_grad_reduce(grads), state.ef
        if quantized:
            # THE data-parallel reduce on the int8 wire: two-stage
            # quantized reduce-scatter + all-gather, residuals carried
            # in the state under int8_ef
            return quantized_pmean_flat(
                grads, axis, key=quant_key(state.step),
                ef=state.ef if grad_compression == "int8_ef" else (),
                chunk=DEFAULT_CHUNK,
            )
        # THE data-parallel step: average grads over the mesh (DDP),
        # on the (optionally bf16-compressed) wire format; one cast
        # round-trip covers both axes.
        local = grads
        grads = lax.pmean(jax.tree_util.tree_map(wire, grads), axis)
        if seq_axis is not None:
            # every seq shard differentiates a full replica of the
            # loss, so local grads sum to n× the true gradient —
            # MEAN over the axis recovers it (verified empirically,
            # tests/test_seq_parallel_training.py)
            grads = lax.pmean(grads, seq_axis)
        return jax.tree_util.tree_map(unwire, grads, local), state.ef

    def _metrics(state: TrainState, loss, logits, labels, grads, new_params):
        """Replica-averaged metrics, fused into the same program."""
        if model_loss is not None:
            # `logits` is the model's stats: hits and positions as sums, and
            # its own counts: summed over replicas, but for what the model
            # put under "maxima", of which the worst replica's is taken
            stats = dict(logits)
            c1, c5, b = stats.pop("top1"), stats.pop("top5"), stats.pop("weight_sum")
            stats.pop("nll_sum")
            maxima = stats.pop("maxima", {})
            extra = {
                **{k: lax.psum(v, batch_axes) for k, v in stats.items()},
                **{k: lax.pmax(v, batch_axes) for k, v in maxima.items()},
            }
        else:
            c1, c5 = F.topk_correct(logits.astype(jnp.float32), labels, (1, 5))
            b, extra = labels.shape[0], {}
        metrics = {
            "loss": lax.pmean(loss, batch_axes),
            "acc1": lax.psum(c1, batch_axes) / (b * lax.psum(1, batch_axes)) * 100.0,
            "acc5": lax.psum(c5, batch_axes) / (b * lax.psum(1, batch_axes)) * 100.0,
            **extra,
        }
        if device_metrics:
            # grads is the post-reduce (post-clip) tree here — the ZeRO-1
            # branch (where it would be a shard) is refused above — so
            # every stat is local arithmetic riding the same fetch
            metrics.update(
                compute_device_stats(grads, state.params, new_params)
            )
        return metrics

    def _ep_grad_reduce(grads):
        """Per-leaf reduction under expert parallelism (rule verified
        empirically, tests/test_expert_parallel_training.py): expert-sharded
        leaves already aggregate the whole expert group's token
        contributions (n_ep× scaled) → pmean over data, divide by n_ep;
        replicated leaves are plain per-shard grads → pmean over both axes.
        """
        n_ep = lax.axis_size(ep_axis)

        def has_ep(spec):
            return any(
                ep_axis in (e if isinstance(e, tuple) else (e,))
                for e in spec
                if e is not None
            )

        def red(g, spec):
            if has_ep(spec):
                return unwire(lax.pmean(wire(g), axis), g) / n_ep
            return unwire(lax.pmean(wire(g), batch_axes), g)

        return jax.tree_util.tree_map(red, grads, param_specs)

    def _sharded_update(state: TrainState, grads, lr):
        """reduce-scatter grads → update own param shard with sharded
        optimizer state → all-gather params (arXiv:2004.13336). Works for
        any optimizer whose update is elementwise over its buffers: SGD's
        momentum rides as one flat vector, AdamW's mu/nu as two (with the
        ``auto`` decay mask converted to a positional per-element vector —
        leaf ranks are invisible in the flat layout).

        Under the int8 modes the reduce-scatter leg carries the quantized
        wire (the one gradient collective in this engine); the param
        all-gather below stays in the param dtype — it moves weights, and
        quantizing weights would drift the replicated copies, a different
        trade than compressing a gradient that feeds a smooth update.
        Returns ``(params, opt_state, ef)``."""
        from jax.flatten_util import ravel_pytree  # noqa: PLC0415

        with hlo_scopes.scope("step/grad_reduce"):
            if seq_axis is not None:
                # same correction as the plain path: each seq shard holds a
                # full-loss-replica gradient, mean over the axis recovers truth
                grads = jax.tree_util.tree_map(
                    lambda g: unwire(lax.pmean(wire(g), seq_axis), g), grads
                )
            flat_g, _ = ravel_pytree(grads)
            flat_p, unravel = ravel_pytree(state.params)
            L = flat_g.shape[0]
            chunk = -(-L // n_axis)
            pad = chunk * n_axis - L
            new_ef = state.ef
            if quantized:
                x = jnp.pad(flat_g / n_axis, (0, pad))
                if grad_compression == "int8_ef":
                    x = x + state.ef["r1"]
                g_shard, sent = _quantized_reduce_scatter_rows(
                    x.reshape(n_axis, chunk), axis,
                    quant_key(state.step), DEFAULT_CHUNK,
                )
                if grad_compression == "int8_ef":
                    new_ef = {"r1": x - sent.reshape(chunk * n_axis)}
            else:
                g_shard = lax.psum_scatter(
                    wire(jnp.pad(flat_g / n_axis, (0, pad))), axis,
                    scatter_dimension=0, tiled=True,
                ).astype(flat_g.dtype)
        with hlo_scopes.scope("step/optimizer"):
            if grad_clip_norm > 0.0:  # global norm from shard norms (one psum)
                sq = lax.psum(jnp.sum(jnp.square(g_shard)), axis)
                scale = jnp.minimum(1.0, grad_clip_norm / jnp.maximum(jnp.sqrt(sq), 1e-12))
                g_shard = g_shard * scale
            idx = lax.axis_index(axis)
            p_shard = lax.dynamic_slice_in_dim(jnp.pad(flat_p, (0, pad)), idx * chunk, chunk)
            kw = {}
            if hasattr(optimizer, "leaf_wd_intervals"):
                # AdamW: the rank-based decay mask in flat coordinates — this
                # shard's per-element decay built from static leaf intervals
                # (iota comparisons; never a model-length constant in HBM)
                pos = idx * chunk + jnp.arange(chunk)
                wd_shard = jnp.zeros((chunk,), jnp.float32)
                for start, end, w in optimizer.leaf_wd_intervals(state.params):
                    wd_shard = wd_shard + w * (
                        (pos >= start) & (pos < end)
                    ).astype(jnp.float32)
                kw["wd_tree"] = wd_shard
            new_p_shard, new_b_shard = optimizer.update(
                g_shard, state.opt_state, p_shard, lr, **kw
            )
            flat_new = lax.all_gather(new_p_shard, axis, tiled=True)[:L]
            return unravel(flat_new), new_b_shard, new_ef

    p_spec = param_specs if param_specs is not None else P()
    if shard_weight_update:
        # ZeRO-1 flat layout: one sharded vector per optimizer buffer
        # (SGD momentum, or AdamW mu/nu + replicated count)
        opt_spec = (
            optimizer.flat_state_specs(axis)
            if hasattr(optimizer, "flat_state_specs")
            else P(axis)
        )
    elif hasattr(optimizer, "state_specs"):
        # optimizer state may not mirror the param tree (AdamW's
        # {mu, nu, count}) — ask the optimizer for its layout
        opt_spec = optimizer.state_specs(p_spec)
    else:
        opt_spec = p_spec
    state_spec = TrainState(
        params=p_spec,
        bn_state=P(),
        opt_state=opt_spec,
        step=P(),
        ef=ef_state_spec(
            grad_compression, zero1=shard_weight_update, axis=axis
        ),
    )
    batch_spec = P(batch_axes)
    sharded = shard_map(
        step_local,
        mesh=mesh,
        in_specs=(state_spec, batch_spec, batch_spec, P()),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def init_sharded_opt_state(
    params, mesh: Mesh, axis: str = mesh_lib.DATA_AXIS, optimizer=None,
):
    """Flat, axis-sharded optimizer state for ``shard_weight_update`` steps:
    f32 vectors of ceil(L/n)*n zeros laid over the axis (each replica holds
    its 1/n shard). Default (``optimizer=None``): SGD's single momentum
    vector. An optimizer exposing ``init_flat_state``/``flat_state_specs``
    (AdamW) gets its own flat layout — mu/nu sharded, count replicated."""
    from jax.flatten_util import ravel_pytree  # noqa: PLC0415
    from jax.sharding import NamedSharding  # noqa: PLC0415

    L = ravel_pytree(params)[0].shape[0]
    n = int(mesh.shape[axis])
    chunk = -(-L // n)
    if optimizer is not None and hasattr(optimizer, "init_flat_state"):
        state = optimizer.init_flat_state(chunk * n)
        specs = optimizer.flat_state_specs(axis)
        return jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
            state, specs,
        )
    return jax.device_put(
        jnp.zeros((chunk * n,), jnp.float32), NamedSharding(mesh, P(axis))
    )


def make_eval_step(
    model_apply: Callable,
    mesh: Mesh,
    *,
    compute_dtype=jnp.float32,
    axis=mesh_lib.DATA_AXIS,
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    pp_axis: str | None = None,
    param_specs=None,
    opt_specs=None,
    ef_specs=(),
    model_kwargs: dict | None = None,
    model_loss: Callable | None = None,
):
    """Build ``eval_step(state, images, labels, mask) -> sums``.

    ``model_loss``: as in :func:`make_train_step`; a sequence counts as one
    example, its loss and hits as the means over its positions.

    ``opt_specs``: partition specs for the optimizer state when its TREE
    differs from the param tree (AdamW under TP/EP/PP) — eval never reads
    it, but the shard_map in_specs must still match its structure.
    ``ef_specs``: same story for the error-feedback residuals of the
    ``int8_ef`` wire format (:func:`ef_state_spec`) — eval ignores them,
    the in_specs must still describe their data-axis layout.

    Returns GLOBAL sums (loss·mask, top1, top5, count) so the host can
    divide once at the end — unlike the reference's ``validate()``, which
    averages per-batch averages over padded shards (the double-count noted
    in SURVEY §3.4). ``mask`` is 1.0 for real examples, 0.0 for sampler
    padding.

    ``axis`` may be a tuple of mesh axes: on a 2-D DP×SP mesh pass
    ``("data", "seq")`` so the eval batch shards over EVERY device (eval
    needs no sequence parallelism — different devices just hold different
    examples).
    """

    def eval_tokens(state: TrainState, tokens, targets, mask):
        _, _, stats = model_loss(
            state.params, state.bn_state, tokens, targets, train=False,
            compute_dtype=compute_dtype, sample_weight=mask, **(model_kwargs or {}),
        )
        per = 1.0 / targets.shape[1]
        return {
            "loss": lax.psum(stats["nll_sum"] * per, axis),
            "top1": lax.psum(stats["top1"] * per, axis),
            "top5": lax.psum(stats["top5"] * per, axis),
            "count": lax.psum(jnp.sum(mask), axis),
        }

    def eval_local(state: TrainState, images, labels, mask):
        x = images.astype(compute_dtype)
        p = jax.tree_util.tree_map(lambda t: t.astype(compute_dtype), state.params)
        kw = {}
        if tp_axis is not None:
            kw["tp_axis"] = tp_axis
        if ep_axis is not None:
            kw["ep_axis"] = ep_axis
        if pp_axis is not None:
            kw["pp_axis"] = pp_axis
        if model_kwargs:
            kw.update(model_kwargs)
        logits, _ = model_apply(p, state.bn_state, x, train=False, axis_name=None, **kw)
        nll = F.cross_entropy(logits, labels, reduction="none")
        maxk_hits = _masked_topk(logits, labels, mask)
        sums = {
            "loss": lax.psum(jnp.sum(nll * mask), axis),
            "top1": lax.psum(maxk_hits[0], axis),
            "top5": lax.psum(maxk_hits[1], axis),
            "count": lax.psum(jnp.sum(mask), axis),
        }
        return sums

    def _masked_topk(logits, labels, mask):
        maxk = min(5, logits.shape[-1])  # clamp: num_classes may be < 5
        _, pred = lax.top_k(logits.astype(jnp.float32), maxk)
        hits = (pred == labels[:, None]).astype(jnp.float32) * mask[:, None]
        return jnp.sum(hits[:, :1]), jnp.sum(hits[:, :maxk])

    p_spec = param_specs if param_specs is not None else P()
    state_spec = TrainState(
        params=p_spec,
        bn_state=P(),
        opt_state=opt_specs if opt_specs is not None else p_spec,
        step=P(),
        ef=ef_specs,
    )
    sharded = shard_map(
        eval_local if model_loss is None else eval_tokens,
        mesh=mesh,
        in_specs=(state_spec, P(axis), P(axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    # eval reads the TrainState without replacing it — donating would free
    # buffers the training loop still owns
    return jax.jit(sharded)  # tpu-dist: ignore[TD003]
