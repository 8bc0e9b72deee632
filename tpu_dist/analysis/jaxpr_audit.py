"""Layer 2 — jaxpr audit of the compiled train steps (rules TD101-TD103).

Where Layer 1 reads *source*, this layer reads the *program*: each
registered audit case builds a real step function on an emulated CPU mesh,
traces it abstractly (``jax.make_jaxpr`` — no device cycles, no
compilation), and walks the closed jaxpr:

* **TD101** — collective ops (``psum``/``all_gather``/``psum_scatter``/
  ``ppermute``/``all_to_all``) are counted and asserted against the
  parallelism config's budget. The budget encodes real invariants: grad
  accumulation must NOT add collectives (torch's ``no_sync`` contract —
  the single post-scan pmean), and ZeRO-1 must replace the grad allreduce
  with exactly one reduce-scatter + one all-gather (arXiv:2004.13336).
* **TD102** — ``device_put`` transfer ops inside the step are host↔device
  traffic on the hot path; the budget is zero.
* **TD103** — bf16→f32 ``convert_element_type`` ops in the mixed-precision
  case are counted against the number the bf16 policy declares (params
  cast transpose + the f32 metric readouts). One more means some op is
  silently promoting — f32 math and double the bytes where bf16 was asked
  for (the promotion-creep failure mode of arXiv:2011.03641 §4).
* **TD104** — static wire-byte accounting of the gradient collectives
  under the compressed wire formats (``grad_compression``): each
  collective eqn is costed with a ring model (``psum`` = reduce-scatter +
  all-gather = 2 payload legs; ``all_to_all``/``reduce_scatter`` = its
  operand once; ``all_gather`` = its output once) and bucketed into
  *payload* (the gradient/param data — int8 under the quantized modes)
  vs *sideband* (quantization scales, scalar metric reduces). The int8
  modes must keep gradient payload ≤0.5× the bf16 mode's and ≤0.25× the
  uncompressed mode's — verified per step for the streaming path and per
  epoch for the fused-``lax.scan`` path. Sideband is reported (never
  hidden) but not gated: the f32 scales are a factor ``chunk`` (256)
  smaller than the payload in ELEMENTS — ``chunk/4`` (64×, ~1.6%) in
  bytes — by construction, independent of the wire format choice.

Counts are per-*equation*: ``lax.pmean`` over a whole grad pytree emits ONE
multi-operand ``psum`` eqn, so budgets stay stable as models grow leaves.

Register additional cases with :func:`register_audit_case` (builders get
the mesh, return ``(fn, example_args, CollectiveBudget)``).
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Callable, Optional

from tpu_dist.analysis.rules import Violation

COLLECTIVE_PRIMS = {
    "psum",
    "pmin",
    "pmax",
    "all_gather",
    "all_to_all",
    "ppermute",
    "pgather",
    "psum_scatter",
    "reduce_scatter",
}
TRANSFER_PRIMS = {"device_put"}


@dataclasses.dataclass
class CollectiveBudget:
    """Expected jaxpr-op counts for one step under one parallelism config.

    ``collectives`` maps primitive name → exact expected eqn count (prims
    absent from the map must not appear at all). ``transfers`` is the
    allowed ``device_put`` count (0 on any sane hot path). ``bf16_to_f32``
    is the declared number of bf16→f32 converts, or None to skip TD103
    (pure-f32 cases)."""

    collectives: dict[str, int]
    transfers: int = 0
    bf16_to_f32: Optional[int] = None


@dataclasses.dataclass
class AuditCase:
    name: str
    # builder(mesh) -> (step_fn, example_args_tuple, CollectiveBudget)
    builder: Callable


_CASES: dict[str, AuditCase] = {}


def register_audit_case(name: str, builder: Callable) -> None:
    _CASES[name] = AuditCase(name, builder)


def registered_cases() -> list[str]:
    return sorted(_CASES)


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------


def _sub_jaxprs(params: dict):
    from jax.extend.core import ClosedJaxpr, Jaxpr  # noqa: PLC0415

    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for item in vals:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def _walk_eqns(jaxpr, mult: int = 1):
    """Yield ``(eqn, multiplicity)`` — ops inside a ``scan`` body run once
    per trip, so their counts are multiplied by the trip count. Without
    this, a grad pmean accidentally moved INSIDE the accumulation scan
    (the exact no_sync violation TD101 exists to catch) would count the
    same as the single post-scan reduce."""
    for eqn in jaxpr.eqns:
        yield eqn, mult
        sub_mult = mult
        if eqn.primitive.name == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1))
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk_eqns(sub, sub_mult)


def _call_site(eqn):
    """The Python stack an eqn was bound from, as a hashable key."""
    tb = getattr(eqn.source_info, "traceback", None)
    if tb is None:
        return None
    return tuple((f.file_name, f.line_num) for f in tb.frames)


def _collective_calls(jaxpr):
    """Yield ``(prim, invars, outvars, multiplicity)`` per collective CALL.

    ``lax.psum(tree)`` / ``lax.pmean(tree)`` bind one eqn PER LEAF since
    JAX 0.5 (one multi-operand eqn before), so a 4-leaf gradient pmean
    reads as four psums in the jaxpr although it is one call in the
    program and XLA's combiner makes it one all-reduce again. The budgets
    and the payload/sideband cut are statements about calls, so adjacent
    eqns of one primitive with equal params bound from the same Python
    stack are folded back into the one call they were (nothing else can
    sit between the leaves of one call; two calls on different lines, or
    with arithmetic between them, stay two)."""
    run = None  # (key, prim, invars, outvars, mult)
    for eqn, mult in _walk_eqns(jaxpr):
        name = eqn.primitive.name
        key = None
        if name in COLLECTIVE_PRIMS:
            key = (name, mult, str(sorted(eqn.params.items(), key=str)),
                   _call_site(eqn))
            if run is not None and run[0] == key and key[3] is not None:
                run[2].extend(eqn.invars)
                run[3].extend(eqn.outvars)
                continue
        if run is not None:
            yield run[1:]
        run = (
            (key, name, list(eqn.invars), list(eqn.outvars), mult)
            if key is not None else None
        )
    if run is not None:
        yield run[1:]


# Per-replica wire legs of each collective under the standard ring model:
# psum (allreduce) = reduce-scatter + all-gather of its operand; the
# scatter/gather/transpose prims each move their payload once. The common
# (n-1)/n send fraction cancels in every ratio TD104 checks, so it is left
# out — these are RELATIVE budgets, not absolute bandwidth estimates.
_WIRE_LEGS = {
    "psum": 2,
    "pmin": 2,
    "pmax": 2,
    "reduce_scatter": 1,
    "psum_scatter": 1,
    "all_to_all": 1,
    "ppermute": 1,
    "all_gather": 1,  # costed on its OUTPUT (operand is the local shard)
    "pgather": 1,
}
# Float collectives at/above this element count are gradient/param payload;
# below it they are sideband (scalar metric reduces). Only used when the
# step has no int8 payload to calibrate against.
_PAYLOAD_MIN_ELEMS = 32


def _call_wire(name, invars, outvars) -> tuple[int, int, bool]:
    """``(elements, bytes_on_wire, is_int)`` for one collective call."""
    import numpy as np

    def total(vars_):
        elems = byts = 0
        for v in vars_:
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", ())
            dt = getattr(aval, "dtype", None)
            n = int(np.prod(shape)) if shape else 1
            elems += n
            byts += n * (np.dtype(dt).itemsize if dt is not None else 4)
        return elems, byts

    in_e, in_b = total(invars)
    out_e, out_b = total(outvars)
    legs = _WIRE_LEGS.get(name, 1)
    # all_gather/pgather: the wire carries the gathered OUTPUT; everything
    # else is costed on what the replica feeds in
    e, b = (out_e, out_b) if name in ("all_gather", "pgather") else (in_e, in_b)
    dt = getattr(getattr(invars[0], "aval", None), "dtype", None)
    # quantized payload is specifically the 8-bit wire (int32 scalar
    # METRIC reduces — correct-count psums — are sideband, not payload)
    is_quant = dt is not None and np.dtype(dt).itemsize == 1
    return max(in_e, out_e), legs * b, is_quant


def _wire_buckets(records) -> dict:
    """Bucket ``(prim, elems, bytes, is_quant, mult)`` collective records
    into payload vs sideband. int8 collectives are always quantized
    payload; other collectives are payload when within a factor 8 of the
    LARGEST message in the step (the gradient/param data, whatever its
    dtype — so the cut is identical across wire modes and a mid-size
    non-gradient reduce, e.g. SyncBN statistics, lands in the same bucket
    under every mode), sideband below it (quantization scales — chunking
    keeps them ≤ payload/16 in elements — and scalar metric reduces)."""
    max_e = max((e for _, e, _, _, _ in records), default=0)
    cut = max(max_e / 8.0, float(_PAYLOAD_MIN_ELEMS))
    payload = quant = side = 0
    by_prim: Counter = Counter()
    for prim, elems, byts, is_q, mult in records:
        by_prim[prim] += byts * mult
        if is_q:
            payload += byts * mult
            quant += byts * mult
        elif elems >= cut:
            payload += byts * mult
        else:
            side += byts * mult
    return {
        "payload_bytes": payload,
        "quantized_payload_bytes": quant,
        "sideband_bytes": side,
        "by_prim": dict(sorted(by_prim.items())),
    }


def trace_counts(fn, *args) -> dict:
    """Abstractly trace ``fn(*args)`` and tally the audited op classes."""
    import jax
    import jax.numpy as jnp

    closed = jax.make_jaxpr(fn)(*args)
    collectives: Counter = Counter()
    transfers = 0
    bf16_to_f32 = 0
    wire_records = []
    for name, invars, outvars, mult in _collective_calls(closed.jaxpr):
        collectives[name] += mult
        elems, byts, is_int = _call_wire(name, invars, outvars)
        wire_records.append((name, elems, byts, is_int, mult))
    for eqn, mult in _walk_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in TRANSFER_PRIMS:
            transfers += mult
        elif name == "convert_element_type":
            (invar,) = eqn.invars
            src = getattr(getattr(invar, "aval", None), "dtype", None)
            dst = eqn.params.get("new_dtype")
            if src == jnp.bfloat16 and dst == jnp.float32:
                bf16_to_f32 += mult
    return {
        "collectives": dict(sorted(collectives.items())),
        "transfers": transfers,
        "bf16_to_f32": bf16_to_f32,
        "wire": _wire_buckets(wire_records),
    }


# --------------------------------------------------------------------------
# The default registered cases: the data-parallel train-step family.
# --------------------------------------------------------------------------


class _AuditMLP:
    """BN-free two-layer MLP: the smallest model with a multi-leaf param
    tree (4 leaves) that still exercises the full step machinery.

    ``classes=16`` keeps the TOTAL param count (480) divisible by every
    emulated mesh width (1/2/4/8), so the quantized wire formats' flat
    padding is zero and the TD104 byte ratios are exact (0.5×/0.25×), not
    0.5×+padding. No budget depends on the head width."""

    in_dim, width, classes = 12, 16, 16

    def init(self, key):
        import jax
        import jax.numpy as jnp

        k1, k2 = jax.random.split(key)
        params = {
            "w1": jax.random.normal(k1, (self.in_dim, self.width), jnp.float32) * 0.1,
            "b1": jnp.zeros((self.width,), jnp.float32),
            "w2": jax.random.normal(k2, (self.width, self.classes), jnp.float32) * 0.1,
            "b2": jnp.zeros((self.classes,), jnp.float32),
        }
        return params, {}

    def apply(self, params, state, x, *, train=False, axis_name=None, **kw):
        import jax.numpy as jnp

        x = x.reshape(x.shape[0], -1)
        h = jnp.maximum(x @ params["w1"] + params["b1"], 0.0)
        return h @ params["w2"] + params["b2"], state


def _dp_setup(mesh, **step_kwargs):
    import jax
    import jax.numpy as jnp

    from tpu_dist.train.optim import SGD
    from tpu_dist.train.state import TrainState
    from tpu_dist.train.step import (
        init_ef_state,
        init_sharded_opt_state,
        make_train_step,
    )

    model = _AuditMLP()
    params, bn = model.init(jax.random.PRNGKey(0))
    opt = SGD(momentum=0.9, weight_decay=1e-4)
    zero1 = bool(step_kwargs.get("shard_weight_update"))
    if zero1:
        opt_state = init_sharded_opt_state(params, mesh)
    else:
        opt_state = opt.init(params)
    state = TrainState(params, bn, opt_state, jnp.zeros((), jnp.int32))
    if step_kwargs.get("grad_compression") == "int8_ef":
        state = state._replace(ef=init_ef_state(params, mesh, zero1=zero1))
    step = make_train_step(model.apply, opt, mesh, sync_bn=False, **step_kwargs)
    n = mesh.devices.size
    batch = 8 * n  # 8 per device: divisible by the accum case's K=4
    images = jax.ShapeDtypeStruct((batch, 2, 2, 3), jnp.float32)
    labels = jax.ShapeDtypeStruct((batch,), jnp.int32)
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    return step, (state, images, labels, lr)


def _fused_setup(mesh, mode: str):
    """The fused-epoch (``train/epoch.py``) twin of :func:`_dp_setup`:
    device-resident dataset sized for 2 scan steps per epoch, so the
    per-trip collective multiplication is exercised."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.train.epoch import make_fused_epoch
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.state import TrainState
    from tpu_dist.train.step import init_ef_state

    model = _AuditMLP()
    params, bn = model.init(jax.random.PRNGKey(0))
    opt = SGD(momentum=0.9, weight_decay=1e-4)
    state = TrainState(params, bn, opt.init(params), jnp.zeros((), jnp.int32))
    if mode == "int8_ef":
        state = state._replace(ef=init_ef_state(params, mesh))
    epoch = make_fused_epoch(
        model.apply, opt, mesh, batch_per_device=4, sync_bn=False,
        compute_dtype=jnp.float32, grad_compression=mode,
    )
    n = mesh.devices.size
    images = jax.ShapeDtypeStruct((8 * n, 2, 2, 3), jnp.uint8)  # 2 steps
    labels = jax.ShapeDtypeStruct((8 * n,), jnp.int32)
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    epoch_idx = jax.ShapeDtypeStruct((), jnp.int32)
    return epoch, (state, images, labels, lr, epoch_idx)


# The plain data-parallel step's collective inventory (per compiled step):
#   psum x4: grad-tree pmean (1 multi-operand eqn), metric loss pmean,
#            acc1 correct-count psum, acc5 correct-count psum
#            (the `psum(1, axis)` device-count terms fold to constants
#            at trace time — no eqn).
_DP_BUDGET = {"psum": 4}
# ZeRO-1 swaps the grad psum for reduce-scatter + param all-gather
# (arXiv:2004.13336): 3 metric psums remain. (lax.psum_scatter lowers to
# the `reduce_scatter` primitive.)
_ZERO1_BUDGET = {"psum": 3, "reduce_scatter": 1, "all_gather": 1}
# The quantized two-stage reduce (EQuARX-style RS+AG, step.py): the grad
# psum becomes int8 all_to_all (payload + scale sideband) followed by int8
# all_gather (payload + sideband) — 4 collective eqns replacing 1, moving
# a quarter of the f32 bytes. 3 metric psums remain. Error feedback is
# pure local arithmetic: int8_ef's budget is IDENTICAL to int8's.
_DP_INT8_BUDGET = {"psum": 3, "all_to_all": 2, "all_gather": 2}
# ZeRO-1 quantized: the reduce-scatter leg is the int8 all_to_all pair;
# the param all-gather stays in the param dtype (weights, not gradients).
_ZERO1_INT8_BUDGET = {"psum": 3, "all_to_all": 2, "all_gather": 1}
# Fused-epoch budgets: per-trip collectives × the 2 scan steps.
_FUSED_STEPS = 2
# bf16 compute declares: 4 bf16→f32 converts from the params-cast transpose
# (one per param leaf, rebuilding f32 grads) + 1 logits→f32 for metrics
# + 1 loss→f32 for the metric pmean.
_BF16_CONVERTS = 6


# The dp/zero1 flag combos come from the ONE config-family registry
# (``train/step.py::SHARD_CONFIG_FAMILIES``) shared with the shardlint
# HLO audit — a family added there is automatically the same flags here,
# so the two static accountings (jaxpr ring model, compiled HLO) always
# describe the same program.


def _family_setup(mesh, family: str):
    from tpu_dist.train.step import family_step_kwargs

    return _dp_setup(mesh, **family_step_kwargs(family))


def _case_dp_sgd(mesh):
    fn, args = _family_setup(mesh, "dp_sgd")
    return fn, args, CollectiveBudget(dict(_DP_BUDGET), bf16_to_f32=None)


def _case_dp_sgd_accum(mesh):
    # torch no_sync contract: K local sub-steps, ONE cross-replica reduce —
    # the budget is IDENTICAL to the K=1 step.
    fn, args = _family_setup(mesh, "dp_sgd_accum4")
    return fn, args, CollectiveBudget(dict(_DP_BUDGET), bf16_to_f32=None)


def _case_dp_bf16(mesh):
    fn, args = _family_setup(mesh, "dp_bf16")
    return fn, args, CollectiveBudget(dict(_DP_BUDGET), bf16_to_f32=_BF16_CONVERTS)


def _case_zero1_sgd(mesh):
    fn, args = _family_setup(mesh, "zero1_sgd")
    return fn, args, CollectiveBudget(dict(_ZERO1_BUDGET), bf16_to_f32=None)


def _case_dp_wire_bf16(mesh):
    # the bf16 WIRE format (grad_compression='bf16'; compute stays f32) —
    # the 2-bytes/element reference point of the TD104 wire ratios. NOT
    # dp_bf16, which is the bf16 COMPUTE policy over an f32 wire.
    fn, args = _family_setup(mesh, "dp_wire_bf16")
    return fn, args, CollectiveBudget(dict(_DP_BUDGET), bf16_to_f32=None)


def _case_dp_int8(mesh):
    fn, args = _family_setup(mesh, "dp_int8")
    return fn, args, CollectiveBudget(dict(_DP_INT8_BUDGET), bf16_to_f32=None)


def _case_dp_int8_ef(mesh):
    fn, args = _family_setup(mesh, "dp_int8_ef")
    return fn, args, CollectiveBudget(dict(_DP_INT8_BUDGET), bf16_to_f32=None)


def _case_zero1_int8(mesh):
    fn, args = _family_setup(mesh, "zero1_int8")
    return fn, args, CollectiveBudget(dict(_ZERO1_INT8_BUDGET), bf16_to_f32=None)


def _case_dp_device_metrics(mesh):
    # --device_metrics: the health scalars (grad/param norm, update ratio,
    # nonfinite count) are computed on the POST-pmean gradients — the
    # collective budget is IDENTICAL to the plain step's (TD107's
    # flag-on half, enforced here through the ordinary TD101 machinery)
    fn, args = _family_setup(mesh, "dp_device_metrics")
    return fn, args, CollectiveBudget(dict(_DP_BUDGET), bf16_to_f32=None)


def _fused_budget(per_step: dict) -> dict:
    return {k: v * _FUSED_STEPS for k, v in per_step.items()}


def _case_fused(mode: str, budget: dict):
    def build(mesh):
        fn, args = _fused_setup(mesh, mode)
        return fn, args, CollectiveBudget(_fused_budget(budget), bf16_to_f32=None)

    return build


register_audit_case("dp_sgd", _case_dp_sgd)
register_audit_case("dp_sgd_accum4", _case_dp_sgd_accum)
register_audit_case("dp_bf16", _case_dp_bf16)
register_audit_case("zero1_sgd", _case_zero1_sgd)
register_audit_case("dp_wire_bf16", _case_dp_wire_bf16)
register_audit_case("dp_int8", _case_dp_int8)
register_audit_case("dp_int8_ef", _case_dp_int8_ef)
register_audit_case("zero1_int8", _case_zero1_int8)
register_audit_case("dp_device_metrics", _case_dp_device_metrics)
register_audit_case("fused_none", _case_fused("none", _DP_BUDGET))
register_audit_case("fused_bf16", _case_fused("bf16", _DP_BUDGET))
register_audit_case("fused_int8", _case_fused("int8", _DP_INT8_BUDGET))
register_audit_case("fused_int8_ef", _case_fused("int8_ef", _DP_INT8_BUDGET))


# --------------------------------------------------------------------------
# Driving + budget comparison
# --------------------------------------------------------------------------


def audit_case(name: str, mesh=None) -> tuple[dict, list[Violation]]:
    from tpu_dist.comm import mesh as mesh_lib

    if name not in _CASES:
        raise ValueError(
            f"unknown audit case {name!r}; registered: {registered_cases()}"
        )
    case = _CASES[name]
    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    fn, args, budget = case.builder(m)
    counts = trace_counts(fn, *args)
    return counts, _compare(name, counts, budget)


# TD104: (quantized case, reference case, max payload-byte ratio). Every
# pair present in a report is checked; equality is allowed (the int8 modes
# land EXACTLY on 0.5×bf16 / 0.25×f32 when the flat padding is zero).
_WIRE_RATIO_CHECKS = (
    ("dp_int8", "dp_wire_bf16", 0.5),
    ("dp_int8", "dp_sgd", 0.25),
    ("dp_int8_ef", "dp_wire_bf16", 0.5),
    ("dp_int8_ef", "dp_sgd", 0.25),
    ("fused_int8", "fused_bf16", 0.5),
    ("fused_int8", "fused_none", 0.25),
    ("fused_int8_ef", "fused_bf16", 0.5),
    ("fused_int8_ef", "fused_none", 0.25),
)


def wire_ratio_violations(report: dict) -> list[Violation]:
    """TD104 over a case→counts report: quantized gradient payload must
    honor the declared fraction of its reference mode's payload."""
    out: list[Violation] = []
    for qcase, ref, lim in _WIRE_RATIO_CHECKS:
        if qcase not in report or ref not in report:
            continue
        qb = report[qcase]["wire"]["payload_bytes"]
        rb = report[ref]["wire"]["payload_bytes"]
        if rb and qb > lim * rb:
            out.append(
                Violation(
                    "TD104",
                    f"<jaxpr:{qcase}>",
                    0,
                    f"gradient-collective payload is {qb} B/step vs "
                    f"{ref}'s {rb} B — exceeds the declared {lim}× wire "
                    "budget of the quantized format (a leg decompressed, "
                    "or padding/scale data leaked into the payload)",
                    snippet=f"payload:{qb}>{lim}x{rb}",
                )
            )
    return out


def fault_noop_violations(mesh=None) -> list[Violation]:
    """TD105: the resilience subsystem's zero-cost contract, checked at the
    program level — trace the data-parallel step with fault injection OFF
    and again with a fully-armed composite ``--fault_plan``, and require
    the two jaxprs to be byte-identical. Every injection point is host-side
    (checkpoint writer, loader producer, trainer step grain); the moment
    someone leaks one into the traced step, this trips."""
    import jax

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.resilience import faults

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    prev = faults.active()
    faults.clear()
    try:
        fn, args = _dp_setup(m)
        base = str(jax.make_jaxpr(fn)(*args))
        faults.install(
            "ckpt_write@call=1:times=2;ckpt_corrupt@epoch=0:mode=bitflip;"
            "nan_loss@step=0;sigterm@step=999999;loader_stall@batch=0;"
            "hang@step=999999:seconds=0.1"
        )
        fn2, args2 = _dp_setup(m)
        armed = str(jax.make_jaxpr(fn2)(*args2))
    finally:
        faults.clear()
        if prev is not None:
            faults.install(prev)
    if base != armed:
        return [
            Violation(
                "TD105",
                "<jaxpr:dp_faults_noop>",
                0,
                "the traced train step CHANGED when a fault plan was armed "
                "— a fault-injection point leaked into the compiled "
                "program; injection must stay host-side "
                "(resilience/faults.py contract)",
                snippet="jaxpr(faults_off) != jaxpr(faults_armed)",
            )
        ]
    return []


def telemetry_noop_violations(mesh=None) -> list[Violation]:
    """TD106: the run-telemetry subsystem's zero-cost contract, checked at
    the program level (the TD105 pattern applied to ``tpu_dist.obs``) —
    trace the data-parallel step with telemetry disarmed, then again with
    the full kit armed (span recorder enabled, counters live and moving, a
    heartbeat beating), and require the two jaxprs to be byte-identical.
    Spans/counters/heartbeat are host-side by construction; the moment an
    instrumentation point leaks a traced op (a timing ``device_get``, a
    counter fed from a tracer), this trips."""
    import os
    import tempfile

    import jax

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.obs import counters, spans
    from tpu_dist.obs.heartbeat import Heartbeat

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    was_enabled = spans.enabled()
    spans.disable()
    hb_path = None
    try:
        fn, args = _dp_setup(m)
        base = str(jax.make_jaxpr(fn)(*args))
        # arm everything the trainer would arm. fresh=False when a live
        # recorder was already armed: the audit must not wipe its
        # undrained buffer or shift its clock origin. The probe counter
        # and heartbeat beats are honest process telemetry (they record
        # that an audit ran), not pollution to scrub.
        spans.enable(fresh=not was_enabled)
        counters.inc("analysis.td106_probes")
        fd, hb_path = tempfile.mkstemp(suffix=".heartbeat.json")
        os.close(fd)
        hb = Heartbeat(hb_path)
        hb.beat(epoch=0, step=0, force=True)
        with spans.span("td106/trace_probe"):
            fn2, args2 = _dp_setup(m)
            armed = str(jax.make_jaxpr(fn2)(*args2))
        hb.sweep()
    finally:
        if was_enabled:
            # re-arm even when the trace raised BEFORE the enable above —
            # the caller's live recorder must not come back disabled
            # (idempotent when the enable did run)
            spans.enable(fresh=False)
        else:
            spans.disable()
            spans.drain()  # discard the probe's own span events
        if hb_path is not None:
            try:
                os.remove(hb_path)
            except FileNotFoundError:
                pass
    if base != armed:
        return [
            Violation(
                "TD106",
                "<jaxpr:dp_telemetry_noop>",
                0,
                "the traced train step CHANGED when run telemetry was "
                "armed — an instrumentation point leaked into the compiled "
                "program; spans/counters/heartbeat must stay host-side "
                "(tpu_dist.obs contract, docs/observability.md)",
                snippet="jaxpr(telemetry_off) != jaxpr(telemetry_armed)",
            )
        ]
    return []


def device_metrics_noop_violations(mesh=None) -> list[Violation]:
    """TD107: the ``--device_metrics`` cost contract, checked at the
    program level.

    Flag-off half (the TD105/TD106 pattern — armed host machinery vs a
    quiet baseline, NOT two identical traces): the baseline traces the
    default step with nothing armed; then the HOST health layer goes live
    — the compile-time ``jax.monitoring`` listener installed, an
    ``AnomalyDetector`` observing values, a ``CompileWatcher`` reading
    the executable cache — and an explicit ``device_metrics=False`` step
    is traced under it. The two jaxprs must be byte-identical: anomaly
    detection, cost capture, and compile accounting are host-side by
    construction, and the moment someone "optimizes" a threshold or a
    counter into the traced step, this trips.

    Flag-on half: the pure-DP path's collective AND transfer inventories
    must be unchanged — the health scalars are computed on the post-pmean
    gradients and ride the metrics tree the trainer already fetches, so
    the moment one of them needs its own reduce (or a host transfer),
    this trips. The fetch-count half of the contract (still exactly one
    per-step ``device_get``) is a host-loop property, pinned by the
    trainer-level parity test in ``tests/test_device_health.py``."""
    import jax

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.obs import costmodel
    from tpu_dist.obs.anomaly import AnomalyDetector

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    fn, args = _dp_setup(m)
    base_counts = trace_counts(fn, *args)
    base = str(jax.make_jaxpr(fn)(*args))
    # arm the host health layer, then trace the explicit flag-off step
    costmodel.install_compile_listener()
    det = AnomalyDetector(window=4)
    fn_off, args_off = _dp_setup(m, device_metrics=False)
    watcher = costmodel.CompileWatcher(fn_off)
    for i in range(6):
        det.observe(epoch=0, step=i, loss=1.0 + i, grad_norm=0.5)
        watcher.observe()
    off = str(jax.make_jaxpr(fn_off)(*args_off))
    det.observe(epoch=0, step=99, loss=1e9)  # a firing detector, too
    watcher.observe()
    out: list[Violation] = []
    if base != off:
        out.append(
            Violation(
                "TD107",
                "<jaxpr:dp_device_metrics_noop>",
                0,
                "the traced train step with device_metrics=False under an "
                "armed host health layer (anomaly detector observing, "
                "compile listener + cache watcher live) differs from the "
                "quiet default step — the disabled flag plus the host-side "
                "machinery must be a byte-identical no-op "
                "(obs/device_stats.py contract)",
                snippet="jaxpr(device_metrics_off|health armed) != jaxpr(default)",
            )
        )
    fn_on, args_on = _dp_setup(m, device_metrics=True)
    on_counts = trace_counts(fn_on, *args_on)
    if (
        on_counts["collectives"] != base_counts["collectives"]
        or on_counts["transfers"] != base_counts["transfers"]
    ):
        out.append(
            Violation(
                "TD107",
                "<jaxpr:dp_device_metrics_noop>",
                0,
                "arming --device_metrics changed the pure-DP step's "
                f"collective/transfer inventory (off: "
                f"{base_counts['collectives']}/{base_counts['transfers']} "
                f"→ on: {on_counts['collectives']}/{on_counts['transfers']})"
                " — the health scalars must stay local arithmetic on the "
                "post-pmean gradients",
                snippet=f"collectives:{on_counts['collectives']}",
            )
        )
    return out


def profile_trigger_noop_violations(mesh=None) -> list[Violation]:
    """TD108: the triggered-profiler cost contract, checked at the
    program level (the TD105-TD107 armed-vs-off discipline applied to
    ``obs/profile.py``) — trace the data-parallel step with no profiler,
    then again with a :class:`TriggeredProfiler` ARMED (a health trigger
    has fired, the capture is pending), and again with the capture window
    OPEN (a real ``jax.profiler`` trace in flight), and require all three
    jaxprs to be byte-identical. Arming is host bookkeeping and an open
    window only observes the program XLA already built; the moment
    someone routes a "helpful" marker op or a step-numbering annotation
    through the traced step, this trips."""
    import shutil
    import tempfile

    import jax

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.obs.profile import TriggeredProfiler

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    fn, args = _dp_setup(m)
    base = str(jax.make_jaxpr(fn)(*args))
    tmp = tempfile.mkdtemp(prefix="td108_profile_")
    out: list[Violation] = []
    try:
        prof = TriggeredProfiler(
            tmp, window_steps=2, cooldown_steps=0, max_captures=1
        )
        prof.arm("anomaly_loss_spike")
        fn2, args2 = _dp_setup(m)
        armed = str(jax.make_jaxpr(fn2)(*args2))
        started = prof.on_step(0)  # opens a REAL device-trace window
        capturing = str(jax.make_jaxpr(fn2)(*args2))
        prof.close()
        # a capture-backend failure (no profiler available here) leaves
        # nothing in flight; the armed comparison above still gates
        capture_ran = bool(started and started.get("event") == "start")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if base != armed or (capture_ran and base != capturing):
        out.append(
            Violation(
                "TD108",
                "<jaxpr:dp_profile_trigger_noop>",
                0,
                "the traced train step CHANGED when a profiler trigger "
                "was armed (or a capture window was open) — triggered "
                "profiling must stay control-plane only: host bookkeeping "
                "plus jax.profiler start/stop around the unmodified step "
                "(obs/profile.py contract)",
                snippet="jaxpr(profiler_off) != jaxpr(trigger_armed)",
            )
        )
    return out


def xprof_hook_noop_violations(mesh=None) -> list[Violation]:
    """TD110: the auto-analyze hook's cost contract, checked at the
    program level (the TD105-TD109 armed-vs-off discipline applied to
    ``obs/xprof.py`` via ``obs/profile.py``) — trace the data-parallel
    step with no profiler, then drive a :class:`TriggeredProfiler` whose
    analyze hook is ON through its whole life cycle: armed, capture
    window OPEN (tracing mid-capture), and capture CLOSED — which fires
    the real xprof analysis over the just-written capture directory plus
    the cost-model calibration over its report — and trace again after.
    All four jaxprs must be byte-identical: reading a capture back is
    host-side gzip/JSON crunching, and the moment someone routes a
    "handy" marker op or a calibration probe through the traced step,
    this trips. The probe also asserts the hook actually RAN (a stop
    event carrying ``analysis``/``analysis_error``) when the backend
    could capture — a hook that silently stopped firing would make the
    comparison vacuous."""
    import shutil
    import tempfile

    import jax

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.obs import costmodel
    from tpu_dist.obs.profile import TriggeredProfiler

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    fn, args = _dp_setup(m)
    base = str(jax.make_jaxpr(fn)(*args))
    tmp = tempfile.mkdtemp(prefix="td110_xprof_")
    out: list[Violation] = []
    try:
        prof = TriggeredProfiler(
            tmp, window_steps=2, cooldown_steps=0, max_captures=1,
            analyze=True,
        )
        prof.arm("anomaly_loss_spike")
        fn2, args2 = _dp_setup(m)
        armed = str(jax.make_jaxpr(fn2)(*args2))
        started = prof.on_step(0)  # opens a REAL device-trace window
        # run real device work inside the window so the capture the hook
        # analyzes holds an actual XLA timeline, not an empty trace
        jax.block_until_ready(jax.jit(lambda x: x * 2.0)(jax.numpy.ones((8,))))
        capturing = str(jax.make_jaxpr(fn2)(*args2))
        stopped = prof.on_step(2)  # closes the window → auto-analysis runs
        capture_ran = bool(started and started.get("event") == "start")
        analysis_ran = bool(
            stopped is not None
            and ("analysis" in stopped or "analysis_error" in stopped)
        )
        if analysis_ran and stopped.get("analysis") is not None:
            # the calibration path is part of the armed hook: fold the
            # measured report into drift gauges exactly as the trainer does
            costmodel.publish_calibration(costmodel.calibration(
                {"flops_per_step": 1e9, "bytes_per_step": 1e6},
                stopped["analysis"], steps=2, n_devices=1, peak=1e12,
            ))
        analyzed = str(jax.make_jaxpr(fn2)(*args2))
        prof.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if capture_ran and not analysis_ran:
        out.append(
            Violation(
                "TD110",
                "<jaxpr:dp_xprof_hook_noop>",
                0,
                "the TD110 probe captured a real profiler window but the "
                "auto-analyze hook produced neither an analysis nor an "
                "analysis_error on the stop event — the armed-vs-off "
                "comparison would be vacuous; the hook stopped firing "
                "(obs/profile.py contract)",
                snippet="auto-analyze hook did not fire",
            )
        )
    if base != armed or (
        capture_ran and (base != capturing or base != analyzed)
    ):
        out.append(
            Violation(
                "TD110",
                "<jaxpr:dp_xprof_hook_noop>",
                0,
                "the traced train step CHANGED across the auto-analyze "
                "hook's life cycle (armed / capture open / capture closed "
                "and analyzed + calibration published) — capture read-back "
                "must stay host-side file crunching (obs/xprof.py + "
                "obs/profile.py contract)",
                snippet="jaxpr(no_profiler) != jaxpr(xprof_hook_armed)",
            )
        )
    return out


def flight_recorder_noop_violations(mesh=None) -> list[Violation]:
    """TD113: the crash-forensics cost contract, checked at the program
    level (the TD105-TD112 armed-vs-off discipline applied to
    ``obs/flight.py``) — trace the data-parallel step with nothing
    armed, then arm the FULL forensic kit exactly as ``fit()`` does:
    a :class:`FlightRecorder` writing real ring slots (open + step
    records with counter deltas), the ``sys``/``threading`` excepthook
    wrappers installed, the span-open listener tapping the ring, and
    ``faulthandler`` armed to a crash file with the SIGUSR1 all-threads
    dump registered AND actually fired mid-audit — and trace again. The
    two jaxprs must be byte-identical: forensics is pwrite-at-the-step-
    boundary host I/O, and the moment someone routes a step marker or a
    'helpful' device sync through the traced step, this trips. The
    probe also asserts the kit actually RAN (the ring decodes with
    records; the dump file holds a parseable traceback when the signal
    could be delivered) — a dead recorder would make the comparison
    vacuous."""
    import os
    import shutil
    import signal
    import tempfile

    import jax

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.obs import flight as flight_lib
    from tpu_dist.obs import spans

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    fn, args = _dp_setup(m)
    base = str(jax.make_jaxpr(fn)(*args))
    tmp = tempfile.mkdtemp(prefix="td113_flight_")
    rec = None
    handle = None
    out: list[Violation] = []
    try:
        rec = flight_lib.FlightRecorder(
            os.path.join(tmp, flight_lib.RING_NAME), run_id="td113", rank=0
        )
        rec.install_excepthooks()
        spans.set_open_listener(rec.span_open)
        rec.record("open", world=1)
        handle = flight_lib.arm_faulthandler(
            os.path.join(tmp, flight_lib.STACKS_NAME)
        )
        dumped = False
        if handle is not None and handle.registered:
            os.kill(os.getpid(), signal.SIGUSR1)  # a REAL on-demand dump
            dumped = True
        rec.step(0, 0)
        with spans.span("td113/trace_probe"):
            fn2, args2 = _dp_setup(m)
            armed = str(jax.make_jaxpr(fn2)(*args2))
        rec.step(0, 1)
        ring_path = rec.path
        stacks_path = os.path.join(tmp, flight_lib.STACKS_NAME)
        decoded = flight_lib.decode(ring_path)
        ring_ok = len(decoded["records"]) >= 3 and not decoded["torn_slots"]
        dump_ok = True
        if dumped:
            parsed = flight_lib.read_stack_dump(stacks_path)
            dump_ok = bool(parsed and parsed.get("current"))
    finally:
        spans.clear_open_listener()
        if rec is not None:
            rec.uninstall_excepthooks()
            rec.close()
        if handle is not None:
            flight_lib.disarm_faulthandler(handle)
        shutil.rmtree(tmp, ignore_errors=True)
    if not ring_ok or not dump_ok:
        out.append(
            Violation(
                "TD113",
                "<jaxpr:dp_flight_recorder_noop>",
                0,
                "the TD113 probe armed the forensic kit but it did not "
                "actually run ("
                + ("ring failed to decode its own records" if not ring_ok
                   else "the SIGUSR1 dump produced no parseable "
                        "traceback")
                + ") — the armed-vs-off comparison would be vacuous "
                "(obs/flight.py contract)",
                snippet="flight probe did not fire",
            )
        )
    if base != armed:
        out.append(
            Violation(
                "TD113",
                "<jaxpr:dp_flight_recorder_noop>",
                0,
                "the traced train step CHANGED when crash forensics was "
                "armed (flight ring writing, excepthooks wrapped, span "
                "listener tapped, faulthandler + SIGUSR1 dump live) — "
                "forensics must stay host-side file I/O on the step "
                "boundary (obs/flight.py contract, docs/observability.md "
                "'Crash forensics')",
                snippet="jaxpr(forensics_off) != jaxpr(forensics_armed)",
            )
        )
    return out


def live_export_noop_violations(mesh=None) -> list[Violation]:
    """TD109: the live-telemetry cost contract, checked at the program
    level (the TD105-TD108 armed-vs-off discipline applied to
    ``obs/export.py`` + ``obs/alerts.py``) — trace the data-parallel
    step with nothing armed, then arm the FULL live kit: a
    :class:`MetricsExporter` with a real textfile AND a live HTTP
    ``/metrics`` thread serving scrapes, fed a real exposition, plus an
    :class:`AlertEngine` over the built-in rule library observing
    windows and actually FIRING (a sustained stall-fraction breach, the
    exact acceptance scenario) — and trace again. The two jaxprs must be
    byte-identical: exporting and alerting are host-side string/float
    work on values the trainer already holds, and the moment someone
    routes a threshold check or a gauge through the traced step, this
    trips."""
    import os
    import shutil
    import tempfile
    import urllib.request

    import jax

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.obs import alerts as alerts_lib
    from tpu_dist.obs.export import MetricsExporter

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    fn, args = _dp_setup(m)
    base = str(jax.make_jaxpr(fn)(*args))
    tmp = tempfile.mkdtemp(prefix="td109_export_")
    exporter = None
    try:
        engine = alerts_lib.AlertEngine(alerts_lib.load_rules("default"))
        # sustain the stall-frac breach until the rule FIRES — the engine
        # under test must be in its fired state, not just constructed
        fired = []
        for _ in range(3):
            fired.extend(engine.observe({"data_stall_frac": 0.9, "mfu": 0.8}))
        try:
            exporter = MetricsExporter(
                textfile=os.path.join(tmp, "metrics.prom"), port=0, rank=0
            )
        except OSError:
            # no socket allowed in this sandbox: the textfile half still
            # arms; the scrape below just won't run
            exporter = MetricsExporter(
                textfile=os.path.join(tmp, "metrics.prom"), rank=0
            )
        exporter.update(
            {"train.data_stall_frac": 0.9, "train.steps": 42},
            {"alert_active": engine.active()},
            force=True,
        )
        if exporter.port:
            # a live scrape against the serving thread, mid-audit
            with urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/metrics", timeout=5
            ) as resp:
                resp.read()
        fn2, args2 = _dp_setup(m)
        armed = str(jax.make_jaxpr(fn2)(*args2))
        probe_ok = bool(fired) and bool(engine.active().get("stall_high"))
    finally:
        if exporter is not None:
            exporter.close()
        shutil.rmtree(tmp, ignore_errors=True)
    out: list[Violation] = []
    if not probe_ok:
        out.append(
            Violation(
                "TD109",
                "<jaxpr:dp_live_export_noop>",
                0,
                "the TD109 probe could not put the alert engine into its "
                "fired state (the built-in stall_high rule did not fire "
                "on a sustained breach) — the armed-vs-off comparison "
                "would be vacuous; the alert state machine drifted",
                snippet="alert probe did not fire",
            )
        )
    if base != armed:
        out.append(
            Violation(
                "TD109",
                "<jaxpr:dp_live_export_noop>",
                0,
                "the traced train step CHANGED when the live exporter + "
                "alert engine were armed (exposition published, HTTP "
                "endpoint scraped, rules fired) — live telemetry must "
                "stay host-side (obs/export.py + obs/alerts.py contract, "
                "docs/observability.md)",
                snippet="jaxpr(live_off) != jaxpr(live_armed)",
            )
        )
    return out


def _elastic_noop_probe(mesh=None, *, grow: bool):
    """Shared TD111/TD112 probe machinery: build the OLD world's ZeRO-1 +
    error-feedback state host-side (flat momentum padded for ``n_old``
    devices, ``n_old`` residual rows), save a real checkpoint, restore it
    through the elastic remapper onto a template laid out for ``n_new``
    devices, and trace the ``n_new`` train step with the fresh-start
    state and with the restored one. ``grow=False`` is the TD111 shrink
    direction (``n_old = all devices, n_new = n_old // 2``); ``grow=True``
    mirrors it (``n_old = n // 2, n_new = n`` — the path a probe-triggered
    scale-up or fleet chip receipt resumes through).

    The probe model's raveled length is congruent to 4 mod 8 precisely so
    the extent change reshapes the padded flat layouts (the default audit
    MLP's 480 divides every mesh width, which would make the remap a
    no-op). Returns ``(layouts_differ, remapper_fired, identical)``."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.ckpt import checkpoint as ckpt_lib
    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.comm.quantize import padded_len
    from tpu_dist.elastic.remap import Remapper, params_len
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.state import TrainState
    from tpu_dist.train.step import (
        ef_state_host_zeros,
        init_ef_state,
        init_sharded_opt_state,
        make_train_step,
    )

    devs = (
        list(mesh.devices.ravel()) if mesh is not None else jax.devices()
    )
    if grow:
        n_new = len(devs)
        n_old = max(1, n_new // 2)
    else:
        n_old = len(devs)
        n_new = max(1, n_old // 2)
    mesh_new = mesh_lib.data_parallel_mesh(devs[:n_new])

    class _ElasticMLP(_AuditMLP):
        # classes=12 -> L = 12*16 + 16 + 16*12 + 12 = 412 == 4 (mod 8):
        # padded_len(412, 8) = 416 != 412 = padded_len(412, 4) — the
        # extent change genuinely reshapes the flat layouts
        classes = 12

    model = _ElasticMLP()
    params, bn = model.init(jax.random.PRNGKey(0))
    L = params_len(params)
    params_host = jax.tree_util.tree_map(np.asarray, params)
    mom_old = np.zeros((padded_len(L, n_old),), np.float32)
    mom_old[:L] = np.arange(L, dtype=np.float32) * 1e-3
    ef_old = ef_state_host_zeros(params_host, n_old, zero1=True)
    ef_old = {
        "r1": (np.arange(ef_old["r1"].size) * 1e-6).astype(np.float32)
    }
    st_old = TrainState(
        params_host, {}, mom_old, np.asarray(0, np.int32), ef_old
    )
    tmp = tempfile.mkdtemp(prefix="td112_grow_" if grow else "td111_elastic_")
    try:
        path = ckpt_lib.save(tmp, st_old, epoch=0)
        opt = SGD(momentum=0.9, weight_decay=1e-4)
        state_new = TrainState(
            params, bn,
            init_sharded_opt_state(params, mesh_new),
            jnp.zeros((), jnp.int32),
            init_ef_state(params, mesh_new, zero1=True),
        )
        step = make_train_step(
            model.apply, opt, mesh_new, sync_bn=False,
            shard_weight_update=True, grad_compression="int8_ef",
        )
        b = 8 * n_new
        images = jax.ShapeDtypeStruct((b, 2, 2, 3), jnp.float32)
        labels = jax.ShapeDtypeStruct((b,), jnp.int32)
        lr = jax.ShapeDtypeStruct((), jnp.float32)
        base = str(jax.make_jaxpr(step)(state_new, images, labels, lr))
        remapper = Remapper(L, n_new, n_old=n_old)
        restored = ckpt_lib.restore(path, state_new, remap=remapper)
        resumed = str(jax.make_jaxpr(step)(restored, images, labels, lr))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    layouts_differ = (
        padded_len(L, n_old) != padded_len(L, n_new) or n_old != n_new
    )
    return layouts_differ, bool(remapper.used), base == resumed


def elastic_resume_noop_violations(mesh=None) -> list[Violation]:
    """TD111: elastic resume must be invisible to the compiled program —
    a trainer whose state was RESTORED from a checkpoint written at a
    different dp extent (and remapped by ``tpu_dist/elastic/remap.py``)
    must trace the byte-identical step a fresh-start trainer at the same
    (new) world size traces. Any remap sloppiness — a float64 leak from
    numpy padding, a wrong flat length, a dtype drift — changes the
    avals and trips this; and the probe asserts the remapper actually
    FIRED when the two extents produce different padded lengths (a
    vacuous comparison is itself a violation). Probe machinery shared
    with TD112: :func:`_elastic_noop_probe` (the shrink direction)."""
    layouts_differ, fired, identical = _elastic_noop_probe(mesh, grow=False)
    out: list[Violation] = []
    if layouts_differ and not fired:
        out.append(
            Violation(
                "TD111",
                "<jaxpr:dp_elastic_resume_noop>",
                0,
                "the TD111 probe restored across different dp extents but "
                "the elastic remapper never fired — the armed-vs-fresh "
                "comparison would be vacuous; the restore path stopped "
                "routing shape mismatches through the remap hook",
                snippet="elastic remapper did not fire",
            )
        )
    if not identical:
        out.append(
            Violation(
                "TD111",
                "<jaxpr:dp_elastic_resume_noop>",
                0,
                "the traced train step of an elastic-resumed trainer "
                "differs from a fresh-start trainer at the same (new) "
                "world size — the checkpoint remap leaked into the "
                "compiled program (shape/dtype drift in the remapped "
                "ZeRO-1/EF flat layouts; tpu_dist/elastic/remap.py "
                "contract)",
                snippet="jaxpr(fresh_start) != jaxpr(elastic_resumed)",
            )
        )
    return out


def elastic_grow_noop_violations(mesh=None) -> list[Violation]:
    """TD112: the grow mirror of TD111 — a trainer whose state was
    RESTORED from a checkpoint written at a SMALLER dp extent (saved at
    ``n_old = n_new // 2`` and remapped UP) must trace the byte-identical
    step a fresh-start trainer at the larger world size traces. This is
    the proof the scale-up path rides on (docs/resilience.md "Scale-up &
    fleet scheduling"): the supervisor's probe-triggered grow and the
    fleet scheduler's chip receipts both relaunch ``--resume`` onto MORE
    devices, so the remapper's zero-repad of the ZeRO-1 flat vectors,
    the r1 fold into more replica rows, and the r2 re-pad must reproduce
    exactly the aval layout a fresh construction gets. Probe machinery
    shared with TD111: :func:`_elastic_noop_probe` (extents swapped)."""
    layouts_differ, fired, identical = _elastic_noop_probe(mesh, grow=True)
    out: list[Violation] = []
    if layouts_differ and not fired:
        out.append(
            Violation(
                "TD112",
                "<jaxpr:dp_elastic_grow_noop>",
                0,
                "the TD112 probe restored a smaller-world checkpoint "
                "onto more devices but the elastic remapper never fired "
                "— the armed-vs-fresh comparison would be vacuous; the "
                "restore path stopped routing grow shape mismatches "
                "through the remap hook",
                snippet="elastic grow remapper did not fire",
            )
        )
    if not identical:
        out.append(
            Violation(
                "TD112",
                "<jaxpr:dp_elastic_grow_noop>",
                0,
                "the traced train step of a GROW-resumed trainer (state "
                "saved at a smaller dp extent, remapped up) differs from "
                "a fresh-start trainer at the same larger world size — "
                "the scale-up remap leaked into the compiled program "
                "(shape/dtype drift in the re-laid ZeRO-1/EF flat "
                "layouts; tpu_dist/elastic/remap.py contract)",
                snippet="jaxpr(fresh_start) != jaxpr(grow_resumed)",
            )
        )
    return out


def serving_slo_noop_violations(mesh=None) -> list[Violation]:
    """TD114: the serving observability cost contract, checked at the
    program level (the TD105-TD113 armed-vs-off discipline applied to
    ``tpu_dist/serve``) — trace the bare inference forward step (the
    audit MLP's eval-mode apply on one batch bucket), then arm the FULL
    serve telemetry/SLO kit exactly as the engine's pump loop does:
    streaming latency histograms observing real per-phase samples,
    queue/occupancy/availability gauges published into the registry, the
    SLO alert engine driven into a FIRED state (a breached p99 ceiling
    and a blown deadline), the OpenMetrics histogram exposition rendered
    AND parsed back, and a span open around the re-trace — and trace
    again. The two jaxprs must be byte-identical: serving SLOs are host
    arithmetic on timestamps the pump already takes, and the moment
    someone routes a latency probe or a 'helpful' sync through the
    compiled step, this trips. The probe also asserts the kit actually
    RAN (histograms hold samples, a rule fired, the exposition
    round-trips the count) — a dead stats object would make the
    comparison vacuous."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.obs import counters as counters_lib
    from tpu_dist.obs import export as export_lib
    from tpu_dist.obs import spans
    from tpu_dist.serve import slo as slo_lib

    model = _AuditMLP()
    params, bn = model.init(jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((8, 2, 2, 3), jnp.float32)

    def forward(p, s, images):
        logits, _ = model.apply(p, s, images, train=False)
        return logits

    base = str(jax.make_jaxpr(forward)(params, bn, x))

    stats = slo_lib.ServeStats(deadline_s=0.05)
    engine = slo_lib.make_slo_engine(slo_lib.load_slo_rules("default"))
    fired: list = []
    for _ in range(3):  # 3 windows: sustain=2 rules genuinely sustain
        for _ in range(4):
            stats.on_batch(3, 4)
            # 600 ms total: breaches the 500 ms slo_p99_high ceiling AND
            # the 50 ms probe deadline (availability 0 < 0.999)
            stats.on_request_done(
                0.6, 0.45, {p: 0.1 for p in slo_lib.PHASES}
            )
        stats.set_queue_depth(2)
        window = stats.scalars(window_s=1.0, completed_in_window=4)
        stats.publish(window)
        fired.extend(engine.observe(window))
    exposition = export_lib.render(
        counters_lib.snapshot(),
        {"alert_active": engine.active()},
        histograms=stats.histogram_families(),
    )
    parsed = export_lib.parse(exposition)
    count_key = export_lib.metric_name("serve.latency_seconds") + "_count"
    with spans.span("td114/trace_probe"):
        armed = str(jax.make_jaxpr(forward)(params, bn, x))

    out: list[Violation] = []
    ran = (
        stats.total.count == 12
        and not stats.check_invariants()
        and fired
        and parsed.get(count_key) == 12
    )
    if not ran:
        out.append(
            Violation(
                "TD114",
                "<jaxpr:serving_slo_noop>",
                0,
                "the TD114 probe armed the serve SLO kit but it did not "
                "actually run (histograms empty, invariants broken, no "
                "rule fired, or the exposition failed to round-trip) — "
                "the armed-vs-off comparison would be vacuous "
                "(tpu_dist/serve/slo.py contract)",
                snippet="serve slo probe did not fire",
            )
        )
    if base != armed:
        out.append(
            Violation(
                "TD114",
                "<jaxpr:serving_slo_noop>",
                0,
                "the traced serving forward step CHANGED when the serve "
                "telemetry/SLO machinery was armed (latency histograms "
                "observing, gauges published, SLO rules fired, histogram "
                "exposition rendered, span open) — serving observability "
                "must stay host-side arithmetic around the unmodified "
                "compiled step (tpu_dist/serve contract, docs/serving.md)",
                snippet="jaxpr(bare_inference) != jaxpr(slo_armed)",
            )
        )
    return out


#: A canned TPU-style RESOURCE_EXHAUSTED text the TD115 probe parses —
#: arming the OOM parser is part of the memory kit under audit.
_TD115_OOM_TEXT = (
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm. "
    "Used 15.90G of 15.48G hbm. Exceeded hbm capacity by 430.5M.\n"
    "Largest program allocations in hbm:\n"
    "  1. Size: 2.50G\n"
    '     Operator: op_name="jit(train_step)/dot_general"\n'
    "     Shape: f32[8192,81920]\n"
)


def memory_ledger_noop_violations(mesh=None) -> list[Violation]:
    """TD115: the HBM-observability cost contract, checked at the
    program level (the TD105-TD114 armed-vs-off discipline applied to
    ``obs/memory.py``) — trace the data-parallel step with nothing
    armed, then arm the FULL memory kit exactly as the trainer does:
    the static per-leaf ledger over a real ZeRO-1-sharded state
    (sharded-extent accounting from shardings), the live-buffer census
    over ``jax.live_arrays()``, the allocator ``memory_stats()`` read,
    the census/allocator reconciliation, the ``mem.*`` gauges
    published, the pre-flight feasibility check priced against a real
    budget, the ``memory_analysis()`` waterfall of an AOT-compiled
    probe, and the RESOURCE_EXHAUSTED parser over a canned TPU OOM
    text — and trace again. The two jaxprs must be byte-identical:
    the whole ledger is shape/sharding metadata arithmetic, and the
    moment someone routes a byte-counting probe or a 'helpful' sync
    through the traced step, this trips. The probe also asserts the
    kit actually RAN (non-empty ledger, the reconciliation identity
    holding exactly, a parsed OOM report with the right byte counts) —
    a dead ledger would make the comparison vacuous."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.obs import costmodel
    from tpu_dist.obs import memory as memory_lib

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    # ZeRO-1 case: the state's flat momentum is genuinely sharded, so
    # the ledger's sharded-extent accounting is exercised, not skipped
    fn, args = _dp_setup(m, shard_weight_update=True)
    state = args[0]
    base = str(jax.make_jaxpr(fn)(*args))

    led = memory_lib.static_ledger(
        params=state.params, opt_state=state.opt_state, ef=state.ef,
        bn_state=state.bn_state,
    )
    census = memory_lib.live_census()
    rec = memory_lib.reconcile(census, costmodel.device_memory_stats())
    memory_lib.publish_ledger({
        "static": led, "census": census, "reconciliation": rec,
    })
    feas = memory_lib.feasibility(
        led["bytes_per_device"], budget_bytes=16 * 1024 ** 3, headroom=0.9,
    )
    probe = jax.jit(lambda x: x * 2.0)
    xla = costmodel.memory_analysis_jitted(probe, jnp.ones((64,)))
    oom = memory_lib.parse_resource_exhausted(_TD115_OOM_TEXT)

    fn2, args2 = _dp_setup(m, shard_weight_update=True)
    armed = str(jax.make_jaxpr(fn2)(*args2))

    n = m.devices.size
    ran = (
        led["bytes_per_device"] > 0
        and led["sections"]["opt_state"]["bytes_per_device"] > 0
        and (
            n == 1
            or led["sections"]["opt_state"]["sharded_leaves"] > 0
        )
        and census["n_arrays"] > 0
        and rec["attributed_bytes"] + rec["unattributed_bytes"]
        == rec["bytes_in_use"]
        and feas["fits"]
        and oom is not None
        and oom.get("used_bytes") == int(15.90 * 1024 ** 3)
        and len(oom.get("buffers") or []) == 1
    )
    out: list[Violation] = []
    if not ran:
        out.append(
            Violation(
                "TD115",
                "<jaxpr:dp_memory_ledger_noop>",
                0,
                "the TD115 probe armed the HBM ledger kit but it did "
                "not actually run (empty ledger, no sharded-extent "
                "accounting, broken reconciliation identity, or the "
                "OOM parser returned garbage) — the armed-vs-off "
                "comparison would be vacuous (obs/memory.py contract)",
                snippet="memory ledger probe did not fire",
            )
        )
    if base != armed:
        out.append(
            Violation(
                "TD115",
                "<jaxpr:dp_memory_ledger_noop>",
                0,
                "the traced train step CHANGED when the HBM ledger was "
                "armed (static per-leaf accounting, live census, "
                "allocator reconciliation, gauges, feasibility check, "
                "memory_analysis waterfall, OOM parser) — memory "
                "observability must stay host-side metadata arithmetic "
                "(obs/memory.py contract, docs/observability.md "
                "'HBM ledger & OOM forensics')",
                snippet="jaxpr(ledger_off) != jaxpr(ledger_armed)",
            )
        )
    return out


def tenancy_arbitration_noop_violations(mesh=None) -> list[Violation]:
    """TD122: the multi-tenancy cost contract, checked at the program
    level (the TD105-TD120 armed-vs-off discipline applied to the
    train+serve co-scheduling plane) — trace the data-parallel train
    step AND the serving forward step with nothing armed, then arm the
    FULL tenancy kit exactly as a co-scheduled pod runs it: a breached
    serve exposition (fired ``slo_*`` alerts, queue/availability/p99
    gauges, latency histograms) rendered to disk and scraped back
    through the fleet sensor path (``read_signals``), a kind-aware
    :class:`FleetScheduler` driven through a SUSTAINED breach to a
    genuinely fired ``preempt=True`` donate→grant pair, the cooperative
    SIGTERM flag raised through the installed handler, a live
    :class:`ServingEngine` refusing work under shedding admission, and
    the per-tick chip-second conservation audit — and trace both steps
    again WHILE the preemption flag is up and shedding is on. Both
    jaxprs must be byte-identical: arbitration is host arithmetic over
    scraped files and allocation integers, and the moment someone
    routes a preemption check or an SLO probe through a compiled step,
    this trips. The probe also asserts the kit actually RAN (the scrape
    round-tripped the serve gauges, the preemption decision fired and
    the chips landed, the flag was observed, a request was actually
    shed, the chip-second books balance exactly) — a dead arbiter would
    make the comparison vacuous."""
    import os
    import signal as signal_lib
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.fleet import scheduler as fleet_lib
    from tpu_dist.obs import export as export_lib
    from tpu_dist.obs import heartbeat as heartbeat_lib
    from tpu_dist.resilience import preemption
    from tpu_dist.serve import slo as slo_lib
    from tpu_dist.serve.engine import ServingEngine

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    fn, args = _dp_setup(m, shard_weight_update=True)
    base_train = str(jax.make_jaxpr(fn)(*args))

    model = _AuditMLP()
    params, bn = model.init(jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((8, 2, 2, 3), jnp.float32)

    def forward(p, s, images):
        logits, _ = model.apply(p, s, images, train=False)
        return logits

    base_serve = str(jax.make_jaxpr(forward)(params, bn, x))

    # -- arm: a genuinely breached serve run, scraped off disk --------------
    stats = slo_lib.ServeStats(deadline_s=0.05)
    slo_engine = slo_lib.make_slo_engine(slo_lib.load_slo_rules("default"))
    fired: list = []
    window: dict = {}
    for _ in range(3):  # sustain=2 rules genuinely sustain
        for _ in range(4):
            stats.on_batch(3, 4)
            # 600 ms: breaches slo_p99_high AND the 50 ms deadline
            stats.on_request_done(
                0.6, 0.45, {p: 0.1 for p in slo_lib.PHASES}
            )
        stats.set_queue_depth(6)
        window = stats.scalars(window_s=1.0, completed_in_window=4)
        fired.extend(slo_engine.observe(window))
    with tempfile.TemporaryDirectory(prefix="td122_") as td:
        prom = os.path.join(td, "metrics.prom")
        with open(prom, "w") as f:
            f.write(export_lib.render(
                window,
                {"alert_active": slo_engine.active()},
                histograms=stats.histogram_families(),
            ))
        hb_path = os.path.join(td, "hb.json")
        heartbeat_lib.Heartbeat(hb_path).beat(force=True)
        sig = fleet_lib.read_signals("svc", prom, heartbeat_file=hb_path)

    # -- arm: the kind-aware arbiter, driven to a fired preemption ----------
    sched = fleet_lib.FleetScheduler(
        [
            fleet_lib.RunSpec("trainer", 8, min_procs=2, kind="train"),
            fleet_lib.RunSpec("svc", 4, min_procs=1, kind="serve"),
        ],
        allocations={"trainer": 8, "svc": 2},
    )
    signals = {
        "trainer": fleet_lib.RunSignals(
            run="trainer", data_stall_frac=0.02, goodput_frac=0.9,
            alive=True,
        ),
        "svc": sig,
    }
    decisions: list = []
    tenancy: list = []
    for t in range(1, 5):
        decisions.extend(sched.step(t, signals))
        tenancy.append(sched.tenancy_record(t))
    audit = fleet_lib.audit_chip_seconds(tenancy)

    # -- arm: the cooperative SIGTERM flag + shedding admission -------------
    token = preemption.install()
    engine = ServingEngine(model, params, bn, max_batch=4, max_queue=2)
    try:
        if signal_lib.getsignal(signal_lib.SIGTERM) is preemption._handler:
            signal_lib.raise_signal(signal_lib.SIGTERM)
        else:  # audit driven off the main thread: no handler installed
            preemption._handler(signal_lib.SIGTERM, None)
        flag_fired = preemption.requested()
        engine.set_shedding(True, "vacate (TD122 probe)")
        refused = engine.submit(np.zeros((2, 2, 3), np.float32))
        shed_ok = (
            not refused.ok
            and engine.stats.shed == 1
            and engine.queue_depth() == 0
        )
        # re-trace with the WHOLE kit up: flag raised, shedding on,
        # arbiter holding post-preemption state
        fn2, args2 = _dp_setup(m, shard_weight_update=True)
        armed_train = str(jax.make_jaxpr(fn2)(*args2))
        armed_serve = str(jax.make_jaxpr(forward)(params, bn, x))
    finally:
        engine.set_shedding(False)
        preemption.clear()
        preemption.restore(token)

    out: list[Violation] = []
    ran = (
        sig.queue_depth == 6.0
        and sig.alive is True
        and any(a.startswith("slo_") for a in sig.active_alerts)
        and fired
        and any(d.get("preempt") for d in decisions)
        and sched.preemptions >= 2  # the donate AND the grant
        and sched.alloc == {"trainer": 4, "svc": 4}
        and flag_fired
        and shed_ok
        and audit["conserved"]
    )
    if not ran:
        out.append(
            Violation(
                "TD122",
                "<jaxpr:tenancy_arbitration_noop>",
                0,
                "the TD122 probe armed the tenancy arbitration kit but "
                "it did not actually run (serve gauges failed to scrape, "
                "no slo_* alert fired, the preemption decision never "
                "fired or the chips never landed, the SIGTERM flag was "
                "not observed, no request was shed, or the chip-second "
                "books failed to balance) — the armed-vs-off comparison "
                "would be vacuous (tpu_dist/fleet/scheduler.py contract)",
                snippet="tenancy arbitration probe did not fire",
            )
        )
    if base_train != armed_train:
        out.append(
            Violation(
                "TD122",
                "<jaxpr:tenancy_arbitration_noop>",
                0,
                "the traced train step CHANGED when the multi-tenant "
                "arbitration kit was armed (serve scrape, kind-aware "
                "policy, fired preemption, SIGTERM flag, shedding "
                "admission) — co-scheduling must stay host-side control-"
                "plane arithmetic around the unmodified compiled step "
                "(tpu_dist/fleet/scheduler.py contract, "
                "docs/resilience.md 'Multi-tenant pod')",
                snippet="jaxpr(train, tenancy_off) != jaxpr(train, tenancy_armed)",
            )
        )
    if base_serve != armed_serve:
        out.append(
            Violation(
                "TD122",
                "<jaxpr:tenancy_arbitration_noop>",
                0,
                "the traced serving forward step CHANGED when the multi-"
                "tenant arbitration kit was armed — a replica under an "
                "active vacate (flag up, shedding on) must serve the "
                "SAME compiled program it warmed, or the drain window "
                "retraces exactly when latency matters most "
                "(tpu_dist/serve/engine.py contract, docs/serving.md)",
                snippet="jaxpr(serve, tenancy_off) != jaxpr(serve, tenancy_armed)",
            )
        )
    return out


def pod_hub_noop_violations(mesh=None) -> list[Violation]:
    """TD123: the pod telemetry plane cost contract — trace the
    data-parallel train step AND the serving forward step with nothing
    armed, then arm the FULL telemetry plane exactly as a co-scheduled
    pod runs it: two live run expositions (a healthy trainer, a
    genuinely breached serve run) federated through ONE
    :class:`TelemetryHub` pass with the fleet scheduler's own
    exposition feeding the chip rollups, the arbiter consuming THAT
    snapshot (``signals_from_hub`` — the one fan-in) and driven through
    a sustained breach to a genuinely fired donate→grant pair SHARING
    one ``decision_id``, the id read back off the allocation file,
    stamped into a relaunch env by the supervisor helper, propagated
    into a resume record, and charged by the goodput ledger to
    ``preempt_for_serve_s`` with the bucket partition still exact —
    and trace both steps again mid-audit. Both jaxprs must be
    byte-identical: federation, causal tracing, and attribution are
    host-side file arithmetic, and the moment someone routes a hub
    scrape or a decision-id check through a compiled step, this trips.
    The probe also asserts the plane actually RAN (two runs aggregated,
    the federated page round-trips, the chain holds ONE id across every
    artifact layer, the ledger partition is exact) — zero runs
    aggregated or a chain with no propagated id is itself a
    violation."""
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.elastic import supervisor as supervisor_lib
    from tpu_dist.fleet import capacity as capacity_lib
    from tpu_dist.fleet import scheduler as fleet_lib
    from tpu_dist.obs import export as export_lib
    from tpu_dist.obs import goodput as goodput_lib
    from tpu_dist.obs import heartbeat as heartbeat_lib
    from tpu_dist.obs import hub as hub_lib
    from tpu_dist.serve import slo as slo_lib

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    fn, args = _dp_setup(m, shard_weight_update=True)
    base_train = str(jax.make_jaxpr(fn)(*args))

    model = _AuditMLP()
    params, bn = model.init(jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((8, 2, 2, 3), jnp.float32)

    def forward(p, s, images):
        logits, _ = model.apply(p, s, images, train=False)
        return logits

    base_serve = str(jax.make_jaxpr(forward)(params, bn, x))

    with tempfile.TemporaryDirectory(prefix="td123_") as td:
        # -- arm: two live runs, one healthy and one breached ---------------
        train_prom = os.path.join(td, "trainer.prom")
        with open(train_prom, "w") as f:
            f.write(export_lib.render({
                "train.data_stall_frac": 0.02,
                "goodput.goodput_frac": 0.9,
            }))
        train_hb = os.path.join(td, "trainer.hb")
        heartbeat_lib.Heartbeat(train_hb).beat(force=True)

        stats = slo_lib.ServeStats(deadline_s=0.05)
        slo_engine = slo_lib.make_slo_engine(slo_lib.load_slo_rules("default"))
        window: dict = {}
        for _ in range(3):  # sustain=2 rules genuinely sustain
            for _ in range(4):
                stats.on_batch(3, 4)
                stats.on_request_done(
                    0.6, 0.45, {p: 0.1 for p in slo_lib.PHASES}
                )
            stats.set_queue_depth(6)
            window = stats.scalars(window_s=1.0, completed_in_window=4)
            slo_engine.observe(window)
        svc_prom = os.path.join(td, "svc.prom")
        with open(svc_prom, "w") as f:
            f.write(export_lib.render(
                window,
                {"alert_active": slo_engine.active()},
                histograms=stats.histogram_families(),
            ))
        svc_hb = os.path.join(td, "svc.hb")
        heartbeat_lib.Heartbeat(svc_hb).beat(force=True)

        # -- arm: hub-fed arbiter driven to a chained donate→grant ----------
        fleet_prom = os.path.join(td, "fleet.prom")
        sched = fleet_lib.FleetScheduler(
            [
                fleet_lib.RunSpec("trainer", 8, min_procs=2, kind="train"),
                fleet_lib.RunSpec("svc", 4, min_procs=1, kind="serve"),
            ],
            allocations={"trainer": 8, "svc": 2},
            fleet_dir=td,
        )
        hub = hub_lib.TelemetryHub(
            [
                hub_lib.RunSource(
                    "trainer", metrics_file=train_prom,
                    heartbeat_file=train_hb, kind="train",
                ),
                hub_lib.RunSource(
                    "svc", metrics_file=svc_prom,
                    heartbeat_file=svc_hb, kind="serve",
                ),
            ],
            fleet_exposition=fleet_prom,
        )
        decisions: list = []
        snap: dict = {}
        for t in range(1, 5):
            sched.write_exposition(fleet_prom)
            snap = hub.collect()
            decisions.extend(
                sched.step(t, fleet_lib.signals_from_hub(snap))
            )
        sched.write_exposition(fleet_prom)
        snap = hub.collect()  # scraped MID-AUDIT, post-preemption state
        federated = hub.federated(snap)

        # -- arm: the id crossing every artifact layer ----------------------
        donate = next(
            (d for d in decisions if d.get("action") == "donate"), {}
        )
        grant = next(
            (d for d in decisions if d.get("action") == "grant"
             and d.get("chained")), {}
        )
        did = donate.get("decision_id")
        alloc_meta = capacity_lib.read_allocation_meta(
            sched.allocation_path("trainer")
        )
        env: dict = {}
        supervisor_lib.stamp_decision_env(
            env, sched.allocation_path("trainer")
        )
        env_id = env.get(supervisor_lib.DECISION_ID_ENV)
        env_cause = env.get(supervisor_lib.DECISION_CAUSE_ENV)
        resume_rec = {
            "kind": "resume", "run_id": "b", "ts": 130.0, "rel_s": 10.0,
            "dp": 4, "prev_dp": 8, "resharded": True,
            "decision_id": int(env_id) if env_id else None,
            "decision_cause": env_cause,
        }
        ledger = goodput_lib.run_ledger([
            {"kind": "goodput", "run_id": "a", "ts": 100.0, "final": True,
             "productive_s": 50.0, "data_stall_s": 10.0, "elapsed_s": 60.0},
            resume_rec,
            {"kind": "goodput", "run_id": "b", "ts": 150.0, "final": True,
             "productive_s": 20.0, "elapsed_s": 20.0},
        ]) or {}

        # re-trace with the WHOLE plane up: hub snapshot live, arbiter
        # holding post-preemption state, env stamped, ledger folded
        fn2, args2 = _dp_setup(m, shard_weight_update=True)
        armed_train = str(jax.make_jaxpr(fn2)(*args2))
        armed_serve = str(jax.make_jaxpr(forward)(params, bn, x))

    out: list[Violation] = []
    rollup = snap.get("rollup") or {}
    partition_gap = abs(
        sum(
            ledger.get(f"{b}_s", 0.0) for b in goodput_lib.ALL_BUCKETS
        ) - ledger.get("elapsed_s", -1.0)
    )
    ran = (
        rollup.get("runs_aggregated") == 2  # vacuity guard: ZERO is a trip
        and rollup.get("breach_count") == 1
        and rollup.get("total_chips") == 10.0  # 8 + 2 initial allocations
        and isinstance(rollup.get("last_decision_id"), float)
        and int(rollup["last_decision_id"]) >= 1
        and federated.endswith("# EOF\n")
        and 'run="svc"' in federated
        and "tpu_dist_pod_runs_aggregated 2" in federated
        # the chain: ONE integer id across scheduler ledger, completion
        # grant, allocation file, relaunch env, resume record
        and isinstance(did, int)
        and grant.get("decision_id") == did
        and donate.get("cause") == "serve_breach"
        and alloc_meta.get("decision_id") == did
        and env_id == str(did)
        and resume_rec["decision_id"] == did
        # the attribution: the gap landed in preempt_for_serve_s and
        # the bucket partition stayed EXACT
        and ledger.get("preempt_for_serve_s") == 20.0
        and partition_gap < 1e-6
    )
    if not ran:
        out.append(
            Violation(
                "TD123",
                "<jaxpr:pod_hub_noop>",
                0,
                "the TD123 probe armed the pod telemetry plane but it "
                "did not actually run (fewer than two runs aggregated, "
                "the federated page failed to round-trip, the "
                "donate→grant pair never fired or split across two "
                "decision ids, the id failed to propagate through the "
                "allocation file / relaunch env / resume record, or the "
                "goodput partition broke) — the armed-vs-off comparison "
                "would be vacuous (tpu_dist/obs/hub.py contract)",
                snippet="pod telemetry plane probe did not fire",
            )
        )
    if base_train != armed_train:
        out.append(
            Violation(
                "TD123",
                "<jaxpr:pod_hub_noop>",
                0,
                "the traced train step CHANGED when the pod telemetry "
                "plane was armed (federated hub scrape mid-audit, "
                "hub-fed arbiter, full decision-id chain, serve-preempt "
                "goodput attribution) — the telemetry plane must stay "
                "host-side file arithmetic around the unmodified "
                "compiled step (tpu_dist/obs/hub.py contract, "
                "docs/observability.md 'Pod telemetry hub')",
                snippet="jaxpr(train, hub_off) != jaxpr(train, hub_armed)",
            )
        )
    if base_serve != armed_serve:
        out.append(
            Violation(
                "TD123",
                "<jaxpr:pod_hub_noop>",
                0,
                "the traced serving forward step CHANGED when the pod "
                "telemetry plane was armed — a serve run being scraped "
                "by the hub and preempted by a traced fleet decision "
                "must serve the SAME compiled program it warmed "
                "(tpu_dist/obs/hub.py contract, docs/observability.md "
                "'Pod telemetry hub')",
                snippet="jaxpr(serve, hub_off) != jaxpr(serve, hub_armed)",
            )
        )
    return out


def archive_gate_noop_violations(mesh=None) -> list[Violation]:
    """TD124: the longitudinal-archive cost AND vacuity contract — trace
    the data-parallel train step bare, then arm the FULL archive kit
    exactly as CI runs it: ingest a synthetic bench history (fresh
    captures plus one stale re-emission) into a tempdir archive twice
    (the second pass must append NOTHING — idempotence by fingerprint),
    require the stale copy flagged and excluded from the band, run the
    ``--inject-regression`` probe (a past-band candidate must come back
    REGRESSED, an improvement clean, an injected changepoint localized
    by blame to the exact record), and trace the step again mid-audit.
    The jaxpr must be byte-identical — the archive is host-side file
    arithmetic, and the moment someone routes ingest or a band check
    through a compiled step, this trips. A probe that misses any leg is
    itself a violation: a dead detector silently passes every real
    regression, which is the exact wound (BENCH_r03–r05 re-emissions
    read as fresh) this subsystem exists to close."""
    import json as json_lib
    import os
    import tempfile

    import jax

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.obs import archive as archive_lib

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    fn, args = _dp_setup(m)
    base_train = str(jax.make_jaxpr(fn)(*args))

    out: list[Violation] = []
    path = "<jaxpr:archive_gate_noop>"
    with tempfile.TemporaryDirectory(prefix="td124_") as td:
        # -- arm: a synthetic bench history — 6 fresh captures around
        # 100 img/s plus one stale-stamped re-emission of the last
        bench_path = os.path.join(td, "bench.jsonl")
        recs = []
        for i in range(6):
            recs.append({
                "metric": "synthetic_train_throughput",
                "value": 100.0 + [0.4, -0.3, 0.1, -0.2, 0.3, 0.0][i],
                "unit": "images/sec",
                "capture": {
                    "host": "td124", "bench_run_id": f"run{i:02d}",
                    "mono_s": float(i),
                },
            })
        # the stale re-emission: bench's last-good fallback re-emits the
        # newest capture with its stale stamp (the BENCH_r05 shape)
        recs.append(dict(recs[-1], stale=True, note="re-emitted last good"))
        with open(bench_path, "w") as f:
            for r in recs:
                f.write(json_lib.dumps(r) + "\n")
        arch = os.path.join(td, "archive.jsonl")
        rep1 = archive_lib.ingest_paths([bench_path], arch)
        rep2 = archive_lib.ingest_paths([bench_path], arch)
        records, _counts = archive_lib.load_archive(arch)
        band = archive_lib.band_for(
            records, "synthetic_train_throughput", "value",
        )
        probe = archive_lib.inject_probe(records)

        # -- vacuity guard: every leg must have genuinely fired
        ran = (
            rep1["appended"] == 7
            and rep1["stale_appended"] == 1
            and rep2["appended"] == 0
            and rep2["deduped"] == 7
            and band is not None and band["n"] == 6
            and probe["bands_probed"] >= 1
            and not archive_lib.probe_is_dead(probe)
        )
        if not ran:
            out.append(
                Violation(
                    "TD124",
                    path,
                    0,
                    "the archive-gate probe is VACUOUS or the detector "
                    "is dead: ingest appended "
                    f"{rep1['appended']}/{rep1['stale_appended']}-stale "
                    f"then {rep2['appended']} on re-ingest (want 7/1 "
                    "then 0 — idempotence by fingerprint with the stale "
                    f"re-emission flagged), band n="
                    f"{band['n'] if band else None} (want 6, stale "
                    "excluded), inject-regression probe gate="
                    f"{probe['gate_probe']} improvements_clean="
                    f"{probe['improvements_clean']} changepoint="
                    f"{probe['changepoint_probe']} (want caught/True/"
                    "localized) — a gate that cannot catch its own "
                    "injected regression passes every real one "
                    "(tpu_dist/obs/archive.py)",
                    snippet="inject_probe(archive) came back dead",
                )
            )

    armed_train = str(jax.make_jaxpr(fn)(*args))
    if base_train != armed_train:
        out.append(
            Violation(
                "TD124",
                path,
                0,
                "the traced train step CHANGED when the longitudinal "
                "archive kit was armed (ingest + MAD-band gate + "
                "changepoint blame + injected-regression probe mid-"
                "audit) — the archive must stay host-side file "
                "arithmetic around the unmodified compiled step "
                "(tpu_dist/obs/archive.py, docs/observability.md "
                "'Longitudinal archive & trend gating')",
                snippet="jaxpr(train, archive_off) != "
                        "jaxpr(train, archive_armed)",
            )
        )
    return out


def audit_all(mesh=None, names=None) -> tuple[dict, list[Violation]]:
    """Run every (or the named) registered case. Returns
    ``(report, violations)`` where report maps case → op counts.
    Cross-case TD104 wire-ratio checks run over whichever quantized/
    reference pairs the report contains; full (unfiltered) runs also check
    the TD105 fault-injection, TD106 telemetry, TD107 device-metrics,
    TD108 profiler-trigger, TD109 live-export/alerting, TD110
    capture-auto-analyze, TD111 elastic-resume, TD112 elastic-grow,
    TD113 flight-recorder, TD114 serving-SLO, TD115 memory-ledger,
    TD122 tenancy-arbitration, TD123 pod-telemetry-hub, and TD124
    archive-gate no-op invariants."""
    report: dict = {}
    violations: list[Violation] = []
    for name in names if names is not None else registered_cases():
        counts, vs = audit_case(name, mesh)
        report[name] = counts
        violations.extend(vs)
    violations.extend(wire_ratio_violations(report))
    if names is None:
        vs = fault_noop_violations(mesh)
        report["dp_faults_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = telemetry_noop_violations(mesh)
        report["dp_telemetry_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = device_metrics_noop_violations(mesh)
        report["dp_device_metrics_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = profile_trigger_noop_violations(mesh)
        report["dp_profile_trigger_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = live_export_noop_violations(mesh)
        report["dp_live_export_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = xprof_hook_noop_violations(mesh)
        report["dp_xprof_hook_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = elastic_resume_noop_violations(mesh)
        report["dp_elastic_resume_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = elastic_grow_noop_violations(mesh)
        report["dp_elastic_grow_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = flight_recorder_noop_violations(mesh)
        report["dp_flight_recorder_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = serving_slo_noop_violations(mesh)
        report["serving_slo_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = memory_ledger_noop_violations(mesh)
        report["dp_memory_ledger_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = tenancy_arbitration_noop_violations(mesh)
        report["tenancy_arbitration_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = pod_hub_noop_violations(mesh)
        report["pod_hub_noop"] = {"identical": not vs}
        violations.extend(vs)
        vs = archive_gate_noop_violations(mesh)
        report["archive_gate_noop"] = {"identical": not vs}
        violations.extend(vs)
    return report, violations


def _compare(name: str, counts: dict, budget: CollectiveBudget) -> list[Violation]:
    out: list[Violation] = []
    path = f"<jaxpr:{name}>"
    actual = counts["collectives"]
    for prim in sorted(set(actual) | set(budget.collectives)):
        want, got = budget.collectives.get(prim, 0), actual.get(prim, 0)
        if want != got:
            out.append(
                Violation(
                    "TD101",
                    path,
                    0,
                    f"{prim}: expected {want} per step, jaxpr has {got} — "
                    "the compiled step's collective inventory drifted from "
                    "the parallelism config's budget",
                    snippet=f"{prim}:{got}",
                )
            )
    if counts["transfers"] > budget.transfers:
        out.append(
            Violation(
                "TD102",
                path,
                0,
                f"{counts['transfers']} device_put transfer op(s) inside "
                f"the compiled step (budget {budget.transfers}) — "
                "host↔device traffic on the hot path",
                snippet=f"device_put:{counts['transfers']}",
            )
        )
    if budget.bf16_to_f32 is not None and counts["bf16_to_f32"] != budget.bf16_to_f32:
        out.append(
            Violation(
                "TD103",
                path,
                0,
                f"{counts['bf16_to_f32']} bf16→f32 converts, mixed-precision "
                f"policy declares {budget.bf16_to_f32} — an op is implicitly "
                "promoting to f32",
                snippet=f"bf16_to_f32:{counts['bf16_to_f32']}",
            )
        )
    return out
