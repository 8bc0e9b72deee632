"""From the profiler's ``.xplane.pb`` to device metrics.

``load_xplane`` reads the file with ``jax.profiler.ProfileData`` into plain
lists (``Trace``); everything after that is arithmetic on intervals and is
checked on a recorded trace in ``testdata/`` (``tests/benchmark``). The
interval helpers (``merge_intervals``, ``union_len``, ``intersect_len``,
``self_times``) are copies of ``tpu_dist/obs/xprof.py``'s, which is CPU-tested
and sound but reads chrome-trace JSON that this JAX need not write.

What a v5e trace looks like (read by hand from this PR's first capture, see
``PERF.md``): one plane per chip named ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per executed HLO op, one at a time (a ``while``
spans its body's ops), named by the op's whole HLO text
(``%fusion.46 = bf16[...] fusion(...), kind=kLoop, calls=%fused_computation.70``)
and with no category stat; ``Async XLA Ops`` holds the start-to-done spans of
asynchronous copies and collectives, which overlap compute and are not busy
time; ``XLA Modules`` holds one event per program run and ``Steps`` one per
step. A fusion's name does not say whether it holds a convolution or a dot:
that comes from the compiled program's text (``matmul_computations``). Nor
does a Pallas kernel's: its op is a ``custom-call`` named after the kernel
(``moe_gmm.2``, ``_bwd_pallas.3``), and the same text carries the kernel's
serialized body, which says whether it runs a matrix product
(``mosaic_kernels``).

The host's side is not taken from the profiler. With its host tracer on
(level 1 or 2) the capture of 12 steps is 130-200 MB, the traced epoch starts
with a stall of 1.6-1.8 s and runs at half the rate; with it off (level 0) it
is 4-13 MB and costs 0.6-2% (my chip runs, PR 22). So the harness records its
spans on ``time.perf_counter`` and ``align_host`` puts them on the trace's
clock through the first dispatch, which meets an idle device: the first
program run starts one launch latency (plus the first batch's transfer, at most
some 10 ms) after it, and that is the error of the alignment.
"""

from __future__ import annotations

import base64
import binascii
import glob
import gzip
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]
# {"planes": [{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns]]}]}],
#  "host": {"window": [lo_ns, hi_ns], "spans": [[name, start_ns, end_ns]]}}   (align_host)
Trace = Dict[str, Any]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
COLLECTIVE_STEMS = (
    "all-reduce", "reduce-scatter", "all-gather", "all-to-all",
    "collective-permute", "collective-broadcast",
)
MATMUL_OPCODES = ("convolution", "dot")
LONG_GAP_NS = 20_000.0  # a gap the host could have caused; shorter ones are launch bubbles
# `%name = <shape> opcode(operands), attr=..., calls=%computation`
HLO_EVENT = re.compile(r"^%?(?P<op>[^\s=]+) = (?P<shape>.*?) (?P<opcode>[a-z][a-z0-9\-]*)\(")
HLO_CALLS = re.compile(r"calls=%?([^\s,)]+)")
HLO_KIND = re.compile(r"kind=(k[A-Za-z]+)")
HLO_LAYOUT = re.compile(r"\{[^{}]*\}")
# a Pallas (Mosaic) kernel's op in the compiled text: the instruction's name,
# its target, and its body, base64 of MLIR bytecode, in the backend config
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = ")
MOSAIC_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]*)"')
MLIR_BYTECODE = b"ML\xefR"
# the ops a kernel's body holds where it runs a matrix product on the MXU:
# what ``jnp.dot`` / ``lax.dot_general`` lower to inside a kernel, in the
# bytecode's table of op names (NUL-terminated strings)
MOSAIC_MATMUL = re.compile(rb"(?<![\w.])(?:tpu\.matmul|vector\.contract)\x00")


# -- interval arithmetic (copied from tpu_dist/obs/xprof.py) -------------------

def merge_intervals(ivs: Iterable[Interval]) -> List[Interval]:
    ivs = sorted(ivs)
    if not ivs:
        return []
    out = [list(ivs[0])]
    for a, b in ivs[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_len(ivs: Iterable[Interval]) -> float:
    return sum(b - a for a, b in merge_intervals(ivs))


def intersect_len(a: Iterable[Interval], b: Iterable[Interval]) -> float:
    a, b = merge_intervals(a), merge_intervals(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def self_times(events: List[Tuple[float, float]]) -> List[float]:
    """Self time of each ``(start, end)`` on one line: its duration minus
    what events nested inside it cover (a ``while`` op spans its body's ops)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    out = [events[i][1] - events[i][0] for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        start, end = events[i]
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        if stack and end <= events[stack[-1]][1]:
            out[stack[-1]] -= end - start
        stack.append(i)
    return out


# -- loading --------------------------------------------------------------------

def find_xplane(capture_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(capture_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def load_xplane(path: str) -> Trace:
    """The op and module lines of every chip's plane."""
    import jax  # noqa: PLC0415

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if line.name not in (OP_LINE, MODULE_LINE):
                continue
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                      for ev in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def save_trace(trace: Trace, path: str) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(trace, f, separators=(",", ":"))


def load_trace(path: str) -> Trace:
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)


def describe(trace: Trace, sample: int = 4) -> str:
    """What is in a trace, for reading by hand."""
    out = []
    for plane in trace["planes"]:
        out.append(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            evs = line["events"]
            out.append(f"  LINE {line['name']!r}: {len(evs)} events")
            for name, start, dur in evs[:sample]:
                out.append(f"    {name[:160]} start={start:.0f}ns dur={dur:.0f}ns")
    return "\n".join(out)


# -- reduction --------------------------------------------------------------------

def _line(plane: Dict[str, Any], name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace: Trace) -> List[Dict[str, Any]]:
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(planes, key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def align_host(trace: Trace, window_s: Interval, spans_s, first_dispatch_s: float) -> None:
    """Put the harness's host-clock records (seconds of ``time.perf_counter``)
    on the trace's clock as ``trace["host"]``: the first program run on the
    first chip is taken to start when the first dispatch was made."""
    modules = [s for p in device_planes(trace)[:1] for _, s, _ in _line(p, MODULE_LINE)]
    if not modules:
        return
    offset = min(modules) - first_dispatch_s * 1e9
    trace["host"] = {
        "window": [window_s[0] * 1e9 + offset, window_s[1] * 1e9 + offset],
        "spans": [[n, a * 1e9 + offset, b * 1e9 + offset] for n, a, b in spans_s],
    }


def window_of(trace: Trace) -> Optional[Interval]:
    """The traced window on the trace's clock: the host's, where aligned,
    else from the first device op to the last."""
    host = trace.get("host")
    if host:
        return float(host["window"][0]), float(host["window"][1])
    ops = [(s, s + d) for p in device_planes(trace) for _, s, d in _line(p, OP_LINE)]
    if not ops:
        return None
    return min(s for s, _ in ops), max(e for _, e in ops)


def parse_op(name: str) -> Dict[str, str]:
    """Op name, opcode, fusion kind, called computation and output shape
    from an op line event's name (the op's HLO text)."""
    m = HLO_EVENT.match(name)
    if m is None:
        return {"op": name.lstrip("%"), "opcode": name.lstrip("%").split(".")[0],
                "kind": "", "calls": "", "shape": ""}
    calls, kind = HLO_CALLS.search(name), HLO_KIND.search(name)
    return {"op": m.group("op"), "opcode": m.group("opcode"),
            "kind": kind.group(1) if kind else "",
            "calls": calls.group(1) if calls else "",
            "shape": HLO_LAYOUT.sub("", m.group("shape"))}


def short_name(name: str) -> str:
    """``fusion.46 fusion/kLoop bf16[128,12,196,196]``: what the breakdown
    prints in place of a kilobyte of HLO text."""
    p = parse_op(name)
    text = f"{p['op']} {p['opcode']}" + (f"/{p['kind']}" if p["kind"] else "")
    return (text + (f" {p['shape']}" if p["shape"] else ""))[:120]


def kernel_holds_matmul(instruction: str) -> Optional[bool]:
    """Whether the Mosaic kernel of one ``custom-call`` line of a compiled
    program runs a matrix product; None where its body cannot be read (no
    body, or one that is not MLIR bytecode, as another jax may write it)."""
    m = MOSAIC_BODY.search(instruction)
    if m is None:
        return None
    try:
        body = base64.b64decode(m.group(1), validate=True)
    except (binascii.Error, ValueError):
        return None
    if not body.startswith(MLIR_BYTECODE):
        return None
    return MOSAIC_MATMUL.search(body) is not None


def mosaic_kernels(hlo_text: str) -> Dict[str, Optional[bool]]:
    """Every Pallas kernel's op of a compiled program, by its instruction
    name: ``kernel_holds_matmul`` of each."""
    out: Dict[str, Optional[bool]] = {}
    for line in hlo_text.splitlines():
        if MOSAIC_TARGET in line:
            m = HLO_INSTRUCTION.match(line)
            if m:
                out[m.group(1)] = kernel_holds_matmul(line)
    return out


def matmul_computations(hlo_text: str) -> List[str]:
    """What runs the matrix products of a compiled program: the names of the
    computations whose body holds a convolution or a dot (the fusions that
    call them are the matmul ops), then the names of the Pallas kernels' ops
    whose body holds a matrix product, or cannot be read: such a kernel is
    counted, so that none that holds one is left out."""
    out, current = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{\s*$", line)
        if head:
            current = head.group(1)
        elif line.startswith("}"):
            current = None
        elif current and re.search(r" (convolution|dot)\(", line):
            if not out or out[-1] != current:
                out.append(current)
    return out + [name for name, holds in mosaic_kernels(hlo_text).items() if holds is not False]


def op_kind(name: str, matmuls: Optional[Iterable[str]]) -> str:
    """``collective``, ``matmul`` or ``other``. Collectives count in all
    their forms (``-start``, ``-done``, sync). ``matmuls`` are the names
    ``matmul_computations`` found, a fusion's called computation or a
    kernel's op; None means they are not known."""
    p = parse_op(name)
    if p["opcode"].startswith(COLLECTIVE_STEMS):
        return "collective"
    if p["opcode"] in MATMUL_OPCODES:
        return "matmul"
    if matmuls is not None and (p["calls"] in matmuls
                                or (p["opcode"] == "custom-call" and p["op"] in matmuls)):
        return "matmul"
    return "other"


def kernel_stem(name: str) -> Optional[str]:
    """A ``custom-call`` op's name before its first ``.`` (the kernel's
    name: ``moe_gmm`` of ``moe_gmm.2``); None for any other op."""
    p = parse_op(name)
    return p["op"].split(".")[0] if p["opcode"] == "custom-call" else None


def matmul_split(self_ns_by_op: Dict[str, float], matmuls: Iterable[str],
                 scale: float = 1.0) -> Dict[str, Any]:
    """One chip's self time in matmul ops (``scale`` x its nanoseconds), by
    what runs them: ``xla`` (convolutions, dots and the fusions holding one)
    and ``kernels`` (each Pallas kernel that counts, by ``kernel_stem``);
    beside them, counted in neither, ``other_calls``: the ``custom-call`` ops
    that are no matmul op (a kernel without a matrix product, XLA's own
    custom calls), by stem."""
    matmuls = set(matmuls)
    out: Dict[str, Any] = {"xla": 0.0, "kernels": {}, "other_calls": {}}
    for name, t in self_ns_by_op.items():
        t *= scale
        stem, kind = kernel_stem(name), op_kind(name, matmuls)
        if kind == "matmul" and stem is None:
            out["xla"] += t
        elif stem is not None:
            part = out["kernels" if kind == "matmul" else "other_calls"]
            part[stem] = part.get(stem, 0.0) + t
    return out


def reduce_chip(plane: Dict[str, Any], window: Interval,
                matmuls: Optional[Iterable[str]] = None) -> Dict[str, Any]:
    """One chip's op line inside ``window``, in nanoseconds."""
    lo, hi = window
    matmuls = None if matmuls is None else set(matmuls)
    ops = [
        (n, max(s, lo), min(s + d, hi))
        for n, s, d in _line(plane, OP_LINE)
        if s + d > lo and s < hi
    ]
    selfs = self_times([(s, e) for _, s, e in ops])
    busy = merge_intervals((s, e) for _, s, e in ops)
    kinds = {"collective": 0.0, "matmul": 0.0, "other": 0.0}
    per_op: Dict[str, float] = {}
    collective_ivs, n_collectives = [], 0
    kind_of: Dict[str, str] = {}
    for (name, s, e), t_self in zip(ops, selfs):
        kind = kind_of.get(name) or kind_of.setdefault(name, op_kind(name, matmuls))
        kinds[kind] += t_self
        per_op[name] = per_op.get(name, 0.0) + t_self
        if kind == "collective":
            collective_ivs.append((s, e))
            n_collectives += 1
    return {
        "plane": plane["name"], "n_ops": len(ops),
        "busy_ns": sum(b - a for a, b in busy), "busy_intervals": busy,
        "self_ns_by_kind": kinds, "self_ns_by_op": per_op,
        "collective_ns": union_len(collective_ivs), "n_collectives": n_collectives,
        "n_modules": sum(
            1 for _, s, d in _line(plane, MODULE_LINE) if s + d > lo and s < hi
        ),
    }


def idle_gaps(busy: List[Interval], window: Interval,
              annotations: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle nanoseconds of one chip inside ``window``, by what the host was
    doing: each gap of at least ``LONG_GAP_NS`` goes to the annotation that
    covers most of it, or to ``(no annotation)``; the bubbles between
    consecutive ops of one program are summed as ``(between ops)``."""
    lo, hi = window
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    between = sum(b - a for a, b in gaps if b - a < LONG_GAP_NS)
    gaps = [g for g in gaps if g[1] - g[0] >= LONG_GAP_NS]
    by_name: Dict[str, List[Interval]] = {}
    for name, s, e in annotations:
        by_name.setdefault(name, []).append((s, e))
    by_name = {n: merge_intervals(ivs) for n, ivs in by_name.items()}
    out: Dict[str, float] = {"(between ops)": between} if between else {}
    for gap in gaps:
        best, cover = "(no annotation)", 0.0
        for name, ivs in by_name.items():
            c = intersect_len([gap], ivs)
            if c > cover:
                best, cover = name, c
        out[best] = out.get(best, 0.0) + (gap[1] - gap[0])
    return out


def reduce_trace(trace: Trace, chips: int,
                 matmuls: Optional[Iterable[str]] = None) -> Optional[Dict[str, Any]]:
    """Busy, idle, per-kind and per-op totals of the traced window, in
    seconds; None when the trace holds no device op. ``matmuls``: see
    ``op_kind``; without them ``chip0_matmul_s`` and its ``matmul_split``,
    ``chip0_matmul_split_s``, are None (not measured)."""
    window = window_of(trace)
    planes = device_planes(trace)[:chips]
    if window is None or not planes:
        return None
    per_chip = [reduce_chip(p, window, matmuls) for p in planes]
    if not any(c["n_ops"] for c in per_chip):
        return None
    ns = 1e-9
    window_s = (window[1] - window[0]) * ns
    busy = [c["busy_ns"] * ns for c in per_chip]
    first = per_chip[0]
    top_ops = sorted(first["self_ns_by_op"].items(), key=lambda kv: -kv[1])[:10]
    gaps = idle_gaps(first["busy_intervals"], window,
                     trace.get("host", {}).get("spans", []))
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy_s_max": max(busy), "busy_s_min": min(busy),
        "chip0_busy_s": busy[0],
        "chip0_matmul_s": None if matmuls is None else first["self_ns_by_kind"]["matmul"] * ns,
        "chip0_matmul_split_s": (None if matmuls is None
                                 else matmul_split(first["self_ns_by_op"], matmuls, ns)),
        "chip0_collective_s": first["collective_ns"] * ns,
        "chip0_collectives": first["n_collectives"],
        "chip0_modules": first["n_modules"],
        "device_ops": [[short_name(n), t * ns] for n, t in top_ops],
        "idle_gaps": [[n, t * ns] for n, t in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    }


def cut(trace: Trace, lo: float, hi: float) -> Trace:
    """The events that start inside ``[lo, hi)``: how ``testdata/`` was made."""
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            events = [e for e in line["events"] if lo <= e[1] < hi]
            if events:
                lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    out = {"planes": planes}
    if "host" in trace:
        out["host"] = {"window": [max(lo, trace["host"]["window"][0]), min(hi, trace["host"]["window"][1])],
                       "spans": [sp for sp in trace["host"]["spans"] if sp[2] > lo and sp[1] < hi]}
    return out


def pin(capture_dir: str, cell: str, what: str) -> Trace:
    """A traced run's capture as ``testdata/`` keeps one: the op and module
    lines of ``<capture_dir>``'s ``.xplane.pb`` with what ``program.json``
    beside it says the reduction was given (the host's records, the matmul
    computations). ``cut`` it where a whole capture is too large to keep."""
    trace = load_xplane(find_xplane(capture_dir))
    with open(os.path.join(capture_dir, "program.json"), encoding="utf-8") as f:
        program = json.load(f)
    if program.get("host"):
        trace["host"] = program["host"]
    trace["matmul_computations"] = program["matmul_computations"]
    trace["cut"] = {"cell": cell, "what": what}
    return trace


if __name__ == "__main__":
    import sys

    path = sys.argv[1]
    tr = load_trace(path) if path.endswith(".gz") else load_xplane(path)
    print(describe(tr, sample=int(sys.argv[2]) if len(sys.argv) > 2 else 4))
