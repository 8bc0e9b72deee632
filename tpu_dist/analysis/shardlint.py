"""Layer 3 — static HLO sharding & collective audit (``shardlint``).

The jaxpr layers (TD101-TD115) audit the program the *tracer* saw; this
layer audits the program the *compiler emitted*. Every config family is
lowered through the real ``jax.jit(...).lower(...).compile()`` pipeline
and the post-optimization HLO text is parsed into a structured collective
inventory — op kind, operand/result shapes+dtypes, replica groups,
estimated wire bytes per op under the same ring model TD104 uses — which
is where GSPMD-inserted implicit reshards, surprise all-gathers, and
backend dtype rewrites live, none of which the jaxpr can show.

Two rules ride on the inventory:

* **TD116** ``compiled-collectives-match-predicted`` — the HLO-derived
  wire accounting must agree with the jaxpr-level ring model: total
  elements exactly, integer/quantized legs byte-for-byte, float legs
  exactly in one of the two declared dtype regimes (``native``, or
  ``widened_to_f32`` on backends whose float-normalization pass rewrites
  narrow-float collectives — CPU emulation does exactly this to bf16).
  Anything else means one of the two accountings is lying.
* **TD117** ``unintended-reshard-in-compiled-step`` — any collective the
  prediction did not budget (an unpredicted op *kind*, or per-kind wire
  bytes beyond the prediction) is flagged with op, shape, bytes, and
  replica groups. The canonical trigger is a bad ``in_shardings`` making
  GSPMD gather state the step expected resident
  (:func:`injected_bad_zero1` demonstrates it on the ZeRO-1 step).

Config families come from ONE registry
(``train/step.py::SHARD_CONFIG_FAMILIES``): the dp/zero1/compression
families reuse the jaxpr-audit model zoo; fsdp (GSPMD engine), tp
(Megatron ViT), sp (ring attention), and the serve forward step get
builders here. Each analyzed family lands in ``shard_report.json``
(:func:`build_shard_report` / :func:`load_shard_report`,
docs/shard_report.md): verified
collective inventory + HLO wire bytes + static HBM ledger + calibrated
step-time prediction per family.

Everything is host-side: lowering and compiling for *text* never touches
a device buffer, and on CPU emulation the whole matrix runs in seconds —
a static count that needs no chip.
"""

from __future__ import annotations

import dataclasses
import json
from collections import Counter
from typing import Callable, Optional

from tpu_dist.analysis.rules import Violation

SCHEMA = "shard_report_v1"

#: HLO collective opcodes the inventory tracks (async ``-start`` halves
#: are folded into their base kind; ``-done`` halves are skipped).
HLO_COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

#: jaxpr collective primitive → the HLO opcode it lowers to.
PRIM_TO_HLO_KIND = {
    "psum": "all-reduce",
    "pmin": "all-reduce",
    "pmax": "all-reduce",
    "psum_scatter": "reduce-scatter",
    "reduce_scatter": "reduce-scatter",
    "all_gather": "all-gather",
    "pgather": "all-gather",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
}

#: Per-replica wire legs per HLO kind — the SAME ring model TD104 prices
#: the jaxpr with (``jaxpr_audit._WIRE_LEGS``): an all-reduce is a
#: reduce-scatter + all-gather of its operand (2 legs); the scatter/
#: gather/exchange ops move their costed side once. all-gather is costed
#: on its OUTPUT (the operand is the local shard).
KIND_LEGS = {
    "all-reduce": 2,
    "all-gather": 1,
    "reduce-scatter": 1,
    "all-to-all": 1,
    "collective-permute": 1,
}

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
}
_FLOAT_DTYPES = frozenset(
    d for d in _DTYPE_BYTES if d.startswith(("f", "bf", "c"))
)


class HLOParseError(ValueError):
    """The text is not a parseable post-optimization HLO module (empty,
    truncated mid-computation, or a different dialect entirely)."""


class ShardReportError(ValueError):
    """A shard_report.json failed schema validation on load."""


# --------------------------------------------------------------------------
# The HLO text parser
# --------------------------------------------------------------------------


@dataclasses.dataclass
class HLOCollective:
    """One collective op from the optimized HLO, priced with the ring
    model. ``elems``/``wire_bytes`` already include the loop multiplier
    (``loop_trips`` > 1 for ops living inside a ``while`` body)."""

    kind: str
    shape: str               # costed-side type string, e.g. "f32[12,16]"
    dtype: str
    elems: int               # leg-free element count × loop trips
    wire_bytes: int          # legs × bytes × loop trips
    int_bytes: int           # the integer-dtype share of wire_bytes
    float_bytes: int         # the float-dtype share of wire_bytes
    replica_groups: Optional[str]
    channel_id: Optional[int]
    op_name: str             # metadata op_name (the jax source op)
    source: str              # metadata "file:line" of the jax call site
    computation: str
    in_loop: bool
    loop_trips: int

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


import re  # noqa: E402  (grouped with the parser it serves)

_COMP_NAME_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)")
_WHILE_RE = re.compile(r"\bwhile\(")
_CALLED_RE = re.compile(
    r"(?:body|condition|to_apply|calls|branch_computations)=\{?%?([\w.\-,% ]+)\}?"
)
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:[a-z0-9]*)|pred)\[([0-9,]*)\]")
_KIND_RE = re.compile(
    r"=\s*(.*?)\s+(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\("
)
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[0-9,{} ]*\}\}|\[[0-9,]*\]<=\[[0-9,]*\])"
)
_PAIRS_RE = re.compile(r"source_target_pairs=(\{[0-9,{} ]*\})")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_SOURCE_RE = re.compile(r'source_file="([^"]*)"(?:.*?source_line=(\d+))?')


def _shapes_in(text: str):
    """``(dtype, elems)`` for every type token in ``text`` (unknown
    dtypes are kept with a 4-byte default so a renamed float type drifts
    the bytes instead of vanishing)."""
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        elems = 1
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        out.append((dt, elems))
    return out


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


def _result_shapes(lines: list) -> dict:
    """``{instruction name: [(dtype, elems), ...]}`` for one computation:
    the result type each line defines. Newer XLA prints operands by name
    only (``reduce-scatter(%fusion.3)``), so an operand's shape has to be
    read off the line that defines it."""
    out: dict = {}
    for line in lines:
        m = _DEF_RE.match(line)
        if not m:
            continue
        rhs = m.group(2)
        if rhs.startswith("("):  # tuple type: up to its closing paren
            type_text = "(" + _balanced_operands(rhs, 0) + ")"
        else:
            type_text = rhs.split(" ", 1)[0]
        out[m.group(1)] = _shapes_in(type_text)
    return out


def _balanced_operands(line: str, open_idx: int) -> str:
    """The operand text between the paren at ``open_idx`` and its match
    (TPU tiled layouts like ``{1,0:T(8,128)}`` nest parens)."""
    depth = 0
    for i in range(open_idx, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                return line[open_idx + 1:i]
    return line[open_idx + 1:]


def _split_computations(text: str) -> dict:
    """Module text → ``{computation_name: [body lines]}``; raises
    :class:`HLOParseError` on empty/foreign/truncated input."""
    if not text or not text.strip():
        raise HLOParseError("empty HLO text")
    head = text.lstrip()[:4096]
    if head.startswith("module @") or "stablehlo." in head or "mhlo." in head:
        raise HLOParseError(
            "StableHLO/MLIR dialect — shardlint parses the post-"
            "optimization HLO text (Compiled.as_text()), not the lowered "
            "StableHLO module"
        )
    if "HloModule" not in head:
        raise HLOParseError("no HloModule header — not HLO text")
    comps: dict = {}
    cur: Optional[str] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            # computation headers are the only non-indented lines ending
            # in "{" (the HloModule header is a single self-closed line)
            if (
                line
                and not line[0].isspace()
                and line.endswith("{")
                and not line.startswith("HloModule")
            ):
                m = _COMP_NAME_RE.match(line)
                if m:
                    cur = m.group(1)
                    comps[cur] = []
        elif line == "}":
            cur = None
        else:
            comps[cur].append(line)
    if cur is not None:
        raise HLOParseError(
            f"truncated HLO text: computation {cur!r} never closed"
        )
    if not comps:
        raise HLOParseError("no computations found in HLO text")
    return comps


def _loop_computations(comps: dict) -> set:
    """Names of computations that execute once per loop trip: direct
    ``while`` bodies/conditions plus everything they call, to a fixpoint."""
    called: dict = {}
    loop_roots: set = set()
    for name, lines in comps.items():
        refs: set = set()
        for line in lines:
            for m in _CALLED_RE.finditer(line):
                for part in m.group(1).split(","):
                    refs.add(part.strip().lstrip("%"))
            if _WHILE_RE.search(line):
                wm = re.search(r"body=%?([\w.\-]+)", line)
                cm = re.search(r"condition=%?([\w.\-]+)", line)
                for g in (wm, cm):
                    if g:
                        loop_roots.add(g.group(1))
        called[name] = refs
    loop: set = set()
    frontier = list(loop_roots)
    while frontier:
        name = frontier.pop()
        if name in loop:
            continue
        loop.add(name)
        frontier.extend(called.get(name, ()))
    return loop


def parse_hlo_collectives(
    text: str, *, loop_trips: int = 1
) -> list[HLOCollective]:
    """Parse optimized HLO text into the collective inventory.

    ``loop_trips``: static multiplicity for collectives living inside a
    ``while`` body (XLA's text does not carry trip counts, so the config
    family declares them — a ring-attention family declares its sequence
    extent, a fused-epoch program its steps per epoch). Ops outside loops
    always count once. Raises :class:`HLOParseError` on non-HLO input;
    unknown op names are simply not collectives (a renamed future opcode
    degrades to a smaller inventory, never a crash)."""
    comps = _split_computations(text)
    loop_comps = _loop_computations(comps)
    out: list[HLOCollective] = []
    for comp, lines in comps.items():
        in_loop = comp in loop_comps
        trips = loop_trips if in_loop else 1
        defs = None
        for line in lines:
            m = _KIND_RE.search(line)
            if not m or m.group(3) == "-done":
                continue
            result_part, kind = m.group(1), m.group(2)
            open_idx = m.end(0) - 1
            operand_part = _balanced_operands(line, open_idx)
            attrs = line[open_idx + 1 + len(operand_part):]
            op_shapes = _shapes_in(operand_part)
            if not op_shapes:
                # operands printed by name only: take each one's shape
                # from its defining line (a reduce-scatter's RESULT is
                # 1/n of what the replica feeds in, so the result is no
                # stand-in for the operand)
                if defs is None:
                    defs = _result_shapes(lines)
                op_shapes = [
                    sh for name in _OPERAND_NAME_RE.findall(operand_part)
                    for sh in defs.get(name, ())
                ]
            res_shapes = _shapes_in(result_part)
            if kind == "all-gather":
                # costed on the gathered OUTPUT; async -start results
                # alias the operand in front — drop that prefix
                shapes = res_shapes
                if m.group(3) == "-start" and len(shapes) > len(op_shapes):
                    shapes = shapes[len(op_shapes):]
                shapes = shapes or op_shapes
            else:
                shapes = op_shapes or res_shapes
            elems = sum(n for _, n in shapes)
            legs = KIND_LEGS[kind]
            byts = ints = flts = 0
            for dt, n in shapes:
                b = legs * n * _DTYPE_BYTES.get(dt, 4)
                byts += b
                if dt in _FLOAT_DTYPES or (
                    dt not in _DTYPE_BYTES and dt.startswith("f")
                ):
                    flts += b
                else:
                    ints += b
            groups = _GROUPS_RE.search(attrs)
            pairs = _PAIRS_RE.search(attrs)
            chan = _CHANNEL_RE.search(attrs)
            opn = _OP_NAME_RE.search(attrs)
            src = _SOURCE_RE.search(attrs)
            dom = max(shapes, key=lambda s: s[1])[0] if shapes else "?"
            shape_str = (
                f"{shapes[0][0]}[{shapes[0][1]}]" if len(shapes) == 1
                else "(" + ",".join(f"{d}[{n}]" for d, n in shapes) + ")"
            )
            out.append(
                HLOCollective(
                    kind=kind,
                    shape=shape_str,
                    dtype=dom,
                    elems=elems * trips,
                    wire_bytes=byts * trips,
                    int_bytes=ints * trips,
                    float_bytes=flts * trips,
                    replica_groups=(
                        groups.group(1) if groups
                        else pairs.group(1) if pairs else None
                    ),
                    channel_id=int(chan.group(1)) if chan else None,
                    op_name=(opn.group(1) if opn else "")[:160],
                    source=(
                        f"{src.group(1)}:{src.group(2) or '?'}" if src else ""
                    ),
                    computation=comp,
                    in_loop=in_loop,
                    loop_trips=trips,
                )
            )
    return out


def count_sharding_annotations(stablehlo_text: str) -> int:
    """``custom_call @Sharding`` / ``mhlo.sharding`` annotation count in
    the LOWERED (StableHLO) module — the sharding constraints jax handed
    GSPMD, reported so a family that silently lost its annotations is
    visible in the report."""
    return stablehlo_text.count("@Sharding") + stablehlo_text.count(
        "sdy.sharding_constraint"
    )


# --------------------------------------------------------------------------
# The jaxpr-side prediction (the TD104 ring model, per HLO kind)
# --------------------------------------------------------------------------


def predicted_inventory(fn, *args) -> dict:
    """Abstractly trace ``fn`` and price its collectives with the TD104
    ring model, keyed by the HLO kind each primitive lowers to. Two byte
    flavors per kind: ``bytes`` (the eqn dtypes as traced) and
    ``bytes_f32norm`` (narrow-float legs priced at 4 B/elem — what a
    backend without native narrow-float collectives emits after float
    normalization). Elements are leg-free and dtype-independent — the
    invariant the compiler cannot legally change."""
    import jax
    import numpy as np

    from tpu_dist.analysis.jaxpr_audit import _WIRE_LEGS, _collective_calls

    closed = jax.make_jaxpr(fn)(*args)
    by_kind: dict = {}
    for name, invars, outvars, mult in _collective_calls(closed.jaxpr):
        kind = PRIM_TO_HLO_KIND.get(name, name)
        legs = _WIRE_LEGS.get(name, 1)
        vars_ = outvars if name in ("all_gather", "pgather") else invars
        entry = by_kind.setdefault(
            kind,
            {"eqns": 0, "elems": 0, "bytes": 0, "bytes_f32norm": 0,
             "int_bytes": 0, "float_bytes": 0, "float_bytes_f32norm": 0},
        )
        entry["eqns"] += mult
        for v in vars_:
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", ())
            dt = np.dtype(getattr(aval, "dtype", np.float32))
            n = int(np.prod(shape)) if shape else 1
            isz = dt.itemsize
            is_float = dt.kind in ("f", "c") or dt.name == "bfloat16"
            b = legs * n * isz * mult
            b_norm = legs * n * (max(isz, 4) if is_float else isz) * mult
            entry["elems"] += n * mult
            entry["bytes"] += b
            entry["bytes_f32norm"] += b_norm
            if is_float:
                entry["float_bytes"] += b
                entry["float_bytes_f32norm"] += b_norm
            else:
                entry["int_bytes"] += b
    totals = {
        k: sum(e[k] for e in by_kind.values())
        for k in ("elems", "bytes", "bytes_f32norm", "int_bytes",
                  "float_bytes", "float_bytes_f32norm")
    }
    return {"by_kind": by_kind, "totals": totals, "source": "jaxpr-ring-model"}


def hlo_wire_buckets(ops: list[HLOCollective]) -> dict:
    """Payload/sideband bucketing of the HLO inventory under the SAME
    rule the jaxpr model uses (``jaxpr_audit._wire_buckets``): integer
    legs are always quantized payload, float legs are payload when within
    a factor 8 of the step's largest message, sideband below.

    One normalization first: XLA splits a multi-operand jaxpr eqn (the
    grad-tree pmean) into per-leaf ops, whose small leaves (bias vectors)
    would individually fall under the payload cut the aggregated eqn
    clears — so ops are re-aggregated by their jax call site
    (``kind + metadata op_name + source file:line + dtype``) back to eqn
    granularity, then fed through the one shared bucketing function. The
    two accountings therefore bucket identically by construction."""
    from tpu_dist.analysis.jaxpr_audit import _wire_buckets

    grouped: dict = {}
    for i, op in enumerate(ops):
        is_q = (
            op.int_bytes > 0 and op.float_bytes == 0
            and op.dtype not in ("s32", "u32", "s64", "u64", "pred")
        )
        key = (
            (op.kind, op.op_name, op.source, op.dtype, op.loop_trips)
            if op.op_name or op.source else (op.kind, "anon", i)
        )
        g = grouped.setdefault(key, [op.kind, 0, 0, is_q, op.loop_trips])
        g[1] += op.elems // max(op.loop_trips, 1)
        g[2] += op.wire_bytes // max(op.loop_trips, 1)
        g[3] = g[3] and is_q
    return _wire_buckets([tuple(g) for g in grouped.values()])


# --------------------------------------------------------------------------
# TD116 / TD117 comparison
# --------------------------------------------------------------------------


def _hlo_totals(ops: list[HLOCollective]) -> dict:
    by_kind: dict = {}
    for op in ops:
        e = by_kind.setdefault(
            op.kind, {"ops": 0, "elems": 0, "bytes": 0, "int_bytes": 0,
                      "float_bytes": 0},
        )
        e["ops"] += 1
        e["elems"] += op.elems
        e["bytes"] += op.wire_bytes
        e["int_bytes"] += op.int_bytes
        e["float_bytes"] += op.float_bytes
    totals = {
        k: sum(e[k] for e in by_kind.values())
        for k in ("ops", "elems", "bytes", "int_bytes", "float_bytes")
    }
    return {"by_kind": by_kind, "totals": totals}


def _within(actual: float, expected: float, tol: float) -> bool:
    return abs(actual - expected) <= tol * max(abs(expected), 1.0)


def compare_compiled_vs_predicted(
    name: str,
    ops: list[HLOCollective],
    predicted: dict,
    *,
    tolerance: float = 0.0,
) -> tuple[dict, list[Violation]]:
    """TD116 + TD117 over one family. Returns ``(verdict, violations)``;
    ``verdict`` carries the resolved ``float_wire`` regime and the totals
    both sides agreed (or disagreed) on."""
    path = f"<hlo:{name}>"
    out: list[Violation] = []
    hlo = _hlo_totals(ops)
    pt = predicted["totals"]
    ht = hlo["totals"]

    # -- TD116: elements are dtype-independent and must match exactly ----
    if not _within(ht["elems"], pt["elems"], tolerance):
        out.append(
            Violation(
                "TD116", path, 0,
                f"compiled wire ELEMENTS {ht['elems']} != predicted "
                f"{pt['elems']} (ring model over the jaxpr) — the "
                "compiler moved a different amount of data than the "
                "model budgeted; per-kind: hlo="
                f"{ {k: v['elems'] for k, v in hlo['by_kind'].items()} } "
                f"predicted="
                f"{ {k: v['elems'] for k, v in predicted['by_kind'].items()} }",
                snippet=f"elems:{ht['elems']}!={pt['elems']}",
            )
        )
    # -- TD116: integer (quantized) legs may NEVER change size -----------
    if not _within(ht["int_bytes"], pt["int_bytes"], tolerance):
        out.append(
            Violation(
                "TD116", path, 0,
                f"compiled integer-leg wire bytes {ht['int_bytes']} != "
                f"predicted {pt['int_bytes']} — a quantized leg widened "
                "or leaked (the compiler must not rewrite int8 payload)",
                snippet=f"int_bytes:{ht['int_bytes']}!={pt['int_bytes']}",
            )
        )
    # -- TD116: float legs match in exactly one declared dtype regime ----
    float_wire = None
    if _within(ht["float_bytes"], pt["float_bytes"], tolerance):
        float_wire = "native"
    elif _within(ht["float_bytes"], pt["float_bytes_f32norm"], tolerance):
        float_wire = (
            "widened_to_f32"
            if pt["float_bytes_f32norm"] != pt["float_bytes"]
            else "native"
        )
    else:
        out.append(
            Violation(
                "TD116", path, 0,
                f"compiled float-leg wire bytes {ht['float_bytes']} match "
                f"neither the native prediction {pt['float_bytes']} nor "
                f"the f32-normalized prediction "
                f"{pt['float_bytes_f32norm']} — an undeclared dtype "
                "rewrite on the wire",
                snippet=f"float_bytes:{ht['float_bytes']}",
            )
        )

    # -- TD117: unpredicted kinds / per-kind byte excess ------------------
    for kind, he in sorted(hlo["by_kind"].items()):
        pe = predicted["by_kind"].get(kind)
        if pe is None or pe["elems"] == 0:
            for op in ops:
                if op.kind != kind:
                    continue
                out.append(
                    Violation(
                        "TD117", path, 0,
                        f"unpredicted {op.kind} {op.shape} "
                        f"({op.wire_bytes} wire B, replica_groups="
                        f"{op.replica_groups}, from "
                        f"{op.op_name or '<no metadata>'}) — the jaxpr "
                        "inventory budgets no "
                        f"{kind} here; GSPMD inserted a reshard "
                        "(check in_shardings/out_shardings)",
                        snippet=f"{kind}:{op.shape}",
                    )
                )
            continue
        allowed = max(pe["bytes"], pe["bytes_f32norm"])
        if he["bytes"] > allowed * (1.0 + tolerance) + 0.5:
            excess = he["bytes"] - allowed
            culprits: list[HLOCollective] = []
            acc = 0
            for op in sorted(
                (o for o in ops if o.kind == kind),
                key=lambda o: o.wire_bytes,
            ):
                culprits.append(op)
                acc += op.wire_bytes
                if acc >= excess:
                    break
            desc = ", ".join(
                f"{o.shape}@{o.replica_groups}" for o in culprits[:4]
            )
            out.append(
                Violation(
                    "TD117", path, 0,
                    f"{kind} wire bytes {he['bytes']} exceed the "
                    f"predicted {allowed} by {excess} B — an unintended "
                    f"reshard rides a predicted kind (smallest ops "
                    f"covering the excess: {desc})",
                    snippet=f"{kind}:{he['bytes']}>{allowed}",
                )
            )

    verdict = {
        "float_wire": float_wire,
        "hlo": ht,
        "predicted": pt,
        "agree": not out,
    }
    return verdict, out


def check_expected_kinds(
    name: str, ops: list[HLOCollective], expected_kinds
) -> list[Violation]:
    """TD117 for GSPMD-engine families (no jaxpr prediction exists — the
    partitioner inserts every collective): the emitted kinds must stay
    inside the family's declared set."""
    allowed = set(expected_kinds)
    out: list[Violation] = []
    for op in ops:
        if op.kind in allowed:
            continue
        out.append(
            Violation(
                "TD117", f"<hlo:{name}>", 0,
                f"unexpected {op.kind} {op.shape} ({op.wire_bytes} wire "
                f"B, replica_groups={op.replica_groups}, from "
                f"{op.op_name or '<no metadata>'}) — outside this GSPMD "
                f"family's declared kind set {sorted(allowed)}",
                snippet=f"{op.kind}:{op.shape}",
            )
        )
    return out


# --------------------------------------------------------------------------
# Config families
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ConfigFamily:
    """One shard-auditable configuration: ``build(mesh)`` returns
    ``(step_callable, example_args)`` where ``args[0]`` is the state the
    HBM ledger prices. ``gspmd`` families have no jaxpr prediction (the
    partitioner owns the collectives) and declare ``expected_kinds``
    instead. ``loop_trips`` prices ``while``-resident collectives
    (ring-attention scans); 1 means "collectives must live outside loops"
    — a collective leaking INTO a loop then breaks TD116 by the trip
    factor, which is exactly the no_sync discipline at the HLO level."""

    name: str
    build: Callable
    kind: str = "train"
    gspmd: bool = False
    expected_kinds: tuple = ()
    loop_trips: int = 1
    tolerance: float = 0.0
    min_devices: int = 1
    note: str = ""


_FAMILIES: dict = {}


def register_family(fam: ConfigFamily) -> None:
    _FAMILIES[fam.name] = fam


def registered_families() -> list:
    return sorted(_FAMILIES)


def _mlp_family_builder(family: str):
    def build(mesh):
        from tpu_dist.analysis.jaxpr_audit import _dp_setup
        from tpu_dist.train.step import family_step_kwargs

        return _dp_setup(mesh, **family_step_kwargs(family))

    return build


def _build_fsdp(mesh):
    import jax
    import jax.numpy as jnp

    from tpu_dist.analysis.jaxpr_audit import _AuditMLP
    from tpu_dist.parallel.fsdp import fsdp_specs, make_fsdp_train_step
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.state import TrainState

    model = _AuditMLP()
    params, bn = model.init(jax.random.PRNGKey(0))
    # min_size=64 so the audit MLP's matrices genuinely shard (its leaves
    # sit under the production default threshold)
    specs = fsdp_specs(params, mesh, min_size=64)
    opt = SGD(momentum=0.9, weight_decay=1e-4)
    state = TrainState(params, bn, opt.init(params), jnp.zeros((), jnp.int32))
    step = make_fsdp_train_step(model.apply, opt, mesh, specs, donate=False)
    n = mesh.devices.size
    images = jax.ShapeDtypeStruct((8 * n, 2, 2, 3), jnp.float32)
    labels = jax.ShapeDtypeStruct((8 * n,), jnp.int32)
    return step, (state, images, labels, 0.1)


def _build_tp(mesh):
    import jax
    import jax.numpy as jnp

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.nn.vit import ViTDef
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.state import TrainState
    from tpu_dist.train.step import family_step_kwargs, make_train_step

    devs = list(mesh.devices.ravel())
    n = len(devs)
    m2 = mesh_lib.device_mesh([n // 2, 2], ["data", "model"], devices=devs)
    vit = ViTDef(
        image_size=8, patch_size=4, dim=16, depth=1, heads=2, num_classes=8
    )
    specs = vit.tp_param_specs("model")
    opt = SGD()
    params, s = vit.init(jax.random.PRNGKey(0))
    state = TrainState(params, s, opt.init(params), jnp.zeros((), jnp.int32))
    step = make_train_step(
        vit.apply, opt, m2, sync_bn=False, donate=False,
        param_specs=specs, **family_step_kwargs("tp"),
    )
    b = 4 * (n // 2)
    images = jax.ShapeDtypeStruct((b, 8, 8, 3), jnp.float32)
    labels = jax.ShapeDtypeStruct((b,), jnp.int32)
    return step, (state, images, labels, 0.1)


def _build_sp(mesh):
    import jax
    import jax.numpy as jnp

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.nn.vit import ViTDef
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.state import TrainState
    from tpu_dist.train.step import family_step_kwargs, make_train_step

    devs = list(mesh.devices.ravel())
    n = len(devs)
    m2 = mesh_lib.device_mesh([n // 4, 4], ["data", "seq"], devices=devs)
    vit = ViTDef(
        image_size=8, patch_size=2, dim=16, depth=1, heads=2, num_classes=8
    )
    opt = SGD()
    params, s = vit.init(jax.random.PRNGKey(0))
    state = TrainState(params, s, opt.init(params), jnp.zeros((), jnp.int32))
    step = make_train_step(
        vit.apply, opt, m2, sync_bn=False, donate=False,
        **family_step_kwargs("sp"),
    )
    b = 4 * (n // 4)
    images = jax.ShapeDtypeStruct((b, 8, 8, 3), jnp.float32)
    labels = jax.ShapeDtypeStruct((b,), jnp.int32)
    return step, (state, images, labels, 0.1)


def _build_serve(mesh):
    import jax
    import jax.numpy as jnp

    from tpu_dist.analysis.jaxpr_audit import _AuditMLP
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.state import TrainState
    from tpu_dist.train.step import make_eval_step

    model = _AuditMLP()
    params, bn = model.init(jax.random.PRNGKey(0))
    opt = SGD()
    state = TrainState(params, bn, opt.init(params), jnp.zeros((), jnp.int32))
    step = make_eval_step(model.apply, mesh)
    n = mesh.devices.size
    images = jax.ShapeDtypeStruct((8 * n, 2, 2, 3), jnp.float32)
    labels = jax.ShapeDtypeStruct((8 * n,), jnp.int32)
    mask = jax.ShapeDtypeStruct((8 * n,), jnp.float32)
    return step, (state, images, labels, mask)


for _name in (
    "dp_sgd", "dp_sgd_accum4", "dp_bf16", "dp_wire_bf16",
    "dp_int8", "dp_int8_ef", "zero1_sgd", "zero1_int8",
):
    register_family(ConfigFamily(_name, _mlp_family_builder(_name)))
register_family(ConfigFamily(
    "fsdp", _build_fsdp, gspmd=True,
    expected_kinds=("all-reduce", "all-gather", "reduce-scatter"),
    note="GSPMD engine: collectives are partitioner-inserted; kinds "
         "gated, bytes reported",
))
register_family(ConfigFamily(
    "tp_vit", _build_tp, min_devices=2,
    note="Megatron-TP ViT on [data, model=2]",
))
register_family(ConfigFamily(
    "sp_vit", _build_sp, min_devices=4, loop_trips=4,
    note="ring-attention ViT on [data, seq=4]; ppermutes live in the "
         "ring scan (loop_trips = seq extent)",
))
register_family(ConfigFamily(
    "serve_eval", _build_serve, kind="serve",
    note="the inference/eval forward step (metric psums only)",
))


def injected_bad_zero1(mesh):
    """The TD117 acceptance probe: the ZeRO-1 step re-jitted with a
    deliberately WRONG ``in_shardings`` — params (which the shard_map
    expects replicated) declared sharded over the data axis — so GSPMD
    must insert all-gathers to rebuild them before every step. Returns
    ``(jitted, args)`` for :func:`shard_case`-style analysis; the
    resulting report MUST carry TD117 violations (a clean report here
    means the analyzer stopped seeing reshards)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist.analysis.jaxpr_audit import _dp_setup

    fn, args = _dp_setup(mesh, shard_weight_update=True)
    n = mesh.devices.size

    def bad(x):
        shape = getattr(x, "shape", None)
        if shape and len(shape) >= 1 and shape[0] % n == 0:
            return NamedSharding(mesh, P("data"))
        return NamedSharding(mesh, P())

    state_sh = jax.tree_util.tree_map(bad, args[0])
    batch_sh = NamedSharding(mesh, P("data"))
    jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh, batch_sh, None))
    return jitted, args


# --------------------------------------------------------------------------
# Driving one family / the whole matrix
# --------------------------------------------------------------------------


def _as_jitted(fn):
    import jax

    return fn if hasattr(fn, "lower") else jax.jit(fn)


def shard_case(
    name: str, mesh=None, *, step_override=None
) -> tuple[dict, list[Violation]]:
    """Lower + compile one family, parse the optimized HLO, run
    TD116/TD117, and assemble its shard-report entry.
    ``step_override=(jitted, args)`` swaps in a pre-built step (the
    injected-reshard probe) while keeping the family's prediction."""
    import jax

    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.obs import costmodel

    if name not in _FAMILIES:
        raise ValueError(
            f"unknown config family {name!r}; registered: "
            f"{registered_families()}"
        )
    fam = _FAMILIES[name]
    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    if m.devices.size < fam.min_devices:
        raise ValueError(
            f"family {name!r} needs >= {fam.min_devices} devices "
            f"(got {m.devices.size})"
        )
    fn, args = fam.build(m)
    if step_override is not None:
        jit_fn, args = step_override
    else:
        jit_fn = _as_jitted(fn)
    lowered, compiled = costmodel.lower_and_compile(jit_fn, *args)
    ops = parse_hlo_collectives(
        compiled.as_text(), loop_trips=fam.loop_trips
    )
    hlo = _hlo_totals(ops)
    try:
        annotations = count_sharding_annotations(lowered.as_text())
    except Exception:
        annotations = None

    violations: list[Violation] = []
    predicted = None
    verdict: dict = {}
    if fam.gspmd:
        violations.extend(check_expected_kinds(name, ops, fam.expected_kinds))
        verdict = {
            "float_wire": None,
            "hlo": hlo["totals"],
            "predicted": None,
            "agree": not violations,
            "skipped_td116": "gspmd-engine family: collectives are "
                             "partitioner-inserted, no jaxpr ring model",
        }
    else:
        predicted = predicted_inventory(fn, *args)
        verdict, vs = compare_compiled_vs_predicted(
            name, ops, predicted, tolerance=fam.tolerance
        )
        violations.extend(vs)

    # -- static HBM (the PR 13 ledger) + XLA's executable waterfall ------
    state = args[0]
    hbm: dict = {}
    try:
        from tpu_dist.obs import memory as memory_lib

        led = memory_lib.static_ledger(
            params=getattr(state, "params", None),
            opt_state=getattr(state, "opt_state", None),
            ef=getattr(state, "ef", ()),
            bn_state=getattr(state, "bn_state", None),
        )
        hbm["static_bytes_per_device"] = led["bytes_per_device"]
        hbm["static_sections"] = {
            k: v["bytes_per_device"] for k, v in led["sections"].items()
        }
    except Exception as e:  # pragma: no cover - ledger must never block
        hbm["ledger_error"] = f"{type(e).__name__}: {e}"
    ma = costmodel.memory_analysis_bytes(compiled)
    if ma:
        hbm["memory_analysis"] = ma

    cost = costmodel.step_cost(compiled)
    predicted_step = costmodel.predicted_step_time(
        cost,
        wire_bytes=hlo["totals"]["bytes"],
        n_devices=m.devices.size,
    )

    report = {
        "family": name,
        "kind": fam.kind,
        "config": dict(_family_config(name)),
        "mesh": {ax: int(s) for ax, s in zip(m.axis_names, m.devices.shape)},
        "note": fam.note,
        "collectives": [op.to_json() for op in ops],
        "hlo": {
            **hlo["totals"],
            "by_kind": hlo["by_kind"],
            "wire": hlo_wire_buckets(ops),
            "float_wire": verdict.get("float_wire"),
            "sharding_annotations": annotations,
        },
        "predicted": predicted,
        "verdict": verdict,
        "hbm": hbm,
        "cost": cost,
        "predicted_step": predicted_step,
        "violations": [v.to_json() for v in violations],
    }
    return report, violations


def _family_config(name: str) -> dict:
    from tpu_dist.train.step import SHARD_CONFIG_FAMILIES

    key = {"tp_vit": "tp", "sp_vit": "sp", "serve_eval": None}.get(name, name)
    if key is None:
        return {}
    return SHARD_CONFIG_FAMILIES.get(key, {})


def shard_all(
    mesh=None, names=None
) -> tuple[dict, list[Violation]]:
    """Run the whole family matrix (or ``names``). A family whose build/
    lower/parse fails is recorded under ``skips`` with its typed error —
    never a crash — so a jax upgrade that renames an op degrades the
    report instead of killing the gate; the skip COUNT is loud in the
    report and the CLI output."""
    report: dict = {"families": {}, "skips": {}}
    violations: list[Violation] = []
    for name in names if names is not None else registered_families():
        try:
            fam_report, vs = shard_case(name, mesh)
        except Exception as e:
            report["skips"][name] = f"{type(e).__name__}: {e}"
            continue
        report["families"][name] = fam_report
        violations.extend(vs)
    report["counts"] = {
        "families": len(report["families"]),
        "skipped": len(report["skips"]),
        "violations": len(violations),
    }
    return report, violations


# --------------------------------------------------------------------------
# shard_report.json
# --------------------------------------------------------------------------


def build_shard_report(mesh=None, names=None) -> tuple[dict, list[Violation]]:
    """The persisted artifact: :func:`shard_all` plus environment stamps
    (backend, device kind/count, jax version) and the schema pin."""
    import jax

    report, violations = shard_all(mesh, names)
    dev = jax.devices()[0]
    report = {
        "schema": SCHEMA,
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": jax.device_count(),
        "jax_version": jax.__version__,
        **report,
    }
    return report, violations


def save_shard_report(report: dict, path: str) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    import os

    os.replace(tmp, path)


_REQUIRED_FAMILY_KEYS = (
    "collectives", "hlo", "verdict", "hbm", "cost", "predicted_step",
    "violations",
)

SCHEMA_VERSION = 1
_SCHEMA_TAG_RE = re.compile(r"^shard_report_v(\d+)$")


def load_shard_report(path: str) -> dict:
    """Schema-pinned loader with the summarize ``KNOWN_KINDS`` forward-compat
    discipline: a NEWER ``shard_report_v<N>`` tag is tolerated (every
    schema bump is additive) — its extra fields are ignored and any
    family entry missing the v1 pricing keys is skipped with a count
    into ``load_notes`` rather than read half-blind. A foreign tag, an
    older-than-supported version, or a SAME-version entry missing
    required keys (that is corruption, not forward compat) still raises
    the typed :class:`ShardReportError` — never a silent partial dict."""
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    tag = data.get("schema") if isinstance(data, dict) else None
    m = _SCHEMA_TAG_RE.match(tag) if isinstance(tag, str) else None
    if not isinstance(data, dict) or not m:
        got = tag if isinstance(data, dict) else type(data).__name__
        raise ShardReportError(
            f"{path}: schema {got!r} is not a shard_report tag — "
            "regenerate with `make shard-report`"
        )
    ver = int(m.group(1))
    if ver < SCHEMA_VERSION:
        raise ShardReportError(
            f"{path}: schema {tag!r} predates v{SCHEMA_VERSION} — "
            "regenerate with `make shard-report`"
        )
    newer = ver > SCHEMA_VERSION
    fams = data.get("families")
    if not isinstance(fams, dict):
        raise ShardReportError(f"{path}: no 'families' map")
    skipped: dict = {}
    for name, entry in list(fams.items()):
        missing = [k for k in _REQUIRED_FAMILY_KEYS if k not in entry]
        if not missing:
            continue
        if not newer:
            raise ShardReportError(
                f"{path}: family {name!r} is missing {missing}"
            )
        skipped[name] = missing
        del fams[name]
    if newer:
        data["load_notes"] = {
            "newer_schema": tag,
            "reader_version": SCHEMA_VERSION,
            "skipped_families": skipped,
            "skipped_count": len(skipped),
        }
    return data


def format_text(report: dict) -> str:
    """Terminal rendering of a shard report (one line per family)."""
    lines = [
        f"shardlint: {report['counts']['families']} famil(ies) analyzed"
        + (
            f", {report['counts']['skipped']} SKIPPED"
            if report["counts"]["skipped"] else ""
        )
        + f", {report['counts']['violations']} violation(s)"
    ]
    for name, fam in sorted(report.get("families", {}).items()):
        h = fam["hlo"]
        kinds = ", ".join(
            f"{k}x{v['ops']}" for k, v in sorted(h["by_kind"].items())
        ) or "collective-free"
        step = fam.get("predicted_step") or {}
        pred = step.get("predicted_step_s")
        lines.append(
            f"  {name:<16} {kinds:<52} wire {h['bytes']:>8} B"
            + (f"  float_wire={h['float_wire']}" if h.get("float_wire") else "")
            + (f"  pred_step {pred * 1e3:.3f} ms" if pred else "")
        )
    for name, why in sorted(report.get("skips", {}).items()):
        lines.append(f"  {name:<16} SKIPPED: {why}")
    return "\n".join(lines)
