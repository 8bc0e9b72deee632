"""Profile analytics — read ``jax.profiler`` captures back
(``docs/observability.md`` "Trace analytics").

The triggered profiler (``obs/profile.py``) writes captures a human must
open in Perfetto to learn anything from; this module closes the loop by
parsing the Chrome-trace JSON JAX writes under every capture directory
(``plugins/profile/<run>/<host>.trace.json.gz`` — gzip + JSON, no proto
deps) into a structured attribution report:

* **Per-category device seconds** — every op event on the device track
  is classified (``matmul_conv`` / ``collective`` / ``infeed_outfeed`` /
  ``fusion_other`` / ``host`` runtime bookkeeping) and charged its SELF
  time (duration minus nested children), so the category seconds sum to
  total device busy time by construction — the invariant the tests pin.
* **Comm/compute overlap** — the fraction of collective wall time during
  which compute was also executing (interval-union intersection across
  the device's op threads). Low overlap on a big collective share means
  the schedule serializes communication the mesh layout promised to hide.
* **Collectives by kind**, **top-k ops by self time**, and
  **infeed-stall seconds** (the device idling on host input).

Device-track selection: real accelerator captures carry ``/device:*``
processes, and their ``XLA Ops`` thread is the op line (other device
threads are alternate views of the same time — never summed).
CPU-emulation captures (the test environment) have no device process;
there, XLA op executions are selected by CONTENT — events stamped with
``args.hlo_op``/``hlo_module``, which XLA:CPU scatters across the
``/host:*`` process's pools (Eigen, TFRT client dispatch, even the
inline ``python`` thread) — and runtime bookkeeping is excluded. A
capture with neither is a typed :class:`NoDeviceTrackError`.

Failure posture: this analyzer runs inside the training process (the
auto-analyze hook fires on every capture close), so malformed input must
NEVER crash it — a truncated gzip, a torn JSON tail, or a track-less
trace file becomes a counted drop in a partial report, and only a
capture with NOTHING analyzable raises (a :class:`CaptureError`
subclass the hook catches). Pure stdlib — no jax, no protobuf; the
report runs anywhere the capture directory can be copied to.
"""

from __future__ import annotations

import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple

from tpu_dist.analysis.shardlint import PRIM_TO_HLO_KIND

#: Attribution categories; their seconds sum to ``device_busy_s``.
CATEGORIES = (
    "matmul_conv", "collective", "infeed_outfeed", "fusion_other", "host",
)

#: HLO collective stems (async ``-start``/``-done`` halves fold into the
#: base kind). Order-independent: matching is exact-stem or stem + "-".
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "all-to-all",
    "ragged-all-to-all",
    "reduce-scatter",
    "collective-permute",
    "collective-broadcast",
    "send",
    "recv",
)

#: HLO instruction names are lowercase ``[a-z0-9_.-]``; anything else on
#: an op thread (``ThreadpoolListener::Record``, ``D2D Dispatch``,
#: ``TfrtCpuExecutable::Execute``) is runtime bookkeeping → ``host``.
_HLO_NAME = re.compile(r"^[a-z0-9_.\-]+$")
#: Numeric / rewrite suffixes stripped to recover the op stem
#: (``tanh.11.clone`` → ``tanh``, ``all-reduce.12`` → ``all-reduce``).
_STEM_SUFFIX = re.compile(r"(\.(\d+|clone|remat\d*))+$")

#: Matmul/conv stems. Deliberately NOT a bare ``conv`` prefix — the
#: ubiquitous dtype-cast op ``convert`` must stay in ``fusion_other``.
_MATMUL_STEMS = ("dot", "convolution", "cudnn-conv", "conv-", "conv2d")


# --------------------------------------------------------------------------
# Typed errors — the auto-analyze hook's catch surface.
# --------------------------------------------------------------------------


class CaptureError(Exception):
    """Base: this capture yielded no analyzable device timeline."""

    kind = "capture_error"


class EmptyCaptureError(CaptureError):
    """No ``*.trace.json.gz`` under the capture directory at all."""

    kind = "empty_capture"


class MalformedTraceError(CaptureError):
    """Trace file unreadable: truncated gzip, torn/invalid JSON."""

    kind = "malformed_trace"


class NoDeviceTrackError(CaptureError):
    """The trace parsed but carries no device/XLA-op track to attribute."""

    kind = "no_device_track"


# --------------------------------------------------------------------------
# Classification
# --------------------------------------------------------------------------


def op_stem(name: str) -> str:
    """``all-reduce.12`` → ``all-reduce``; ``tanh.11.clone`` → ``tanh``."""
    return _STEM_SUFFIX.sub("", name)


def collective_kind(name: str) -> Optional[str]:
    """The collective family of an HLO op name, or None. Async halves
    (``all-gather-start.3``) report their base kind — the wire time is
    one transfer however many HLO ops XLA splits it into."""
    stem = op_stem(name)
    for kind in COLLECTIVE_KINDS:
        if stem == kind or stem.startswith(kind + "-"):
            return kind
    # since jax 0.5 XLA names an instruction after the jax primitive that
    # made it (``%psum.7 = f32[] all-reduce(...)``), and a trace event
    # bears the instruction's NAME, not its opcode
    return PRIM_TO_HLO_KIND.get(stem)


def classify(name: str) -> str:
    """Category of one op-thread event name (see :data:`CATEGORIES`)."""
    if not _HLO_NAME.match(name):
        return "host"
    stem = op_stem(name)
    if collective_kind(name) is not None:
        return "collective"
    if stem.startswith("infeed") or stem.startswith("outfeed"):
        return "infeed_outfeed"
    if (
        any(stem.startswith(m) for m in _MATMUL_STEMS)
        or stem == "conv" or "gemm" in stem or "matmul" in stem
    ):
        return "matmul_conv"
    return "fusion_other"


# --------------------------------------------------------------------------
# Interval math
# --------------------------------------------------------------------------


def _self_times_us(events: List[Tuple[float, float, int]]) -> Dict[int, float]:
    """Self time (duration minus nested children, µs) per event index for
    ONE thread's complete events ``(ts, dur, idx)``. Children are clipped
    to their parent, so the per-thread self times sum to the union length
    of the thread's top-level intervals — the invariant that makes the
    category seconds sum to total busy time."""
    out: Dict[int, float] = {}
    stack: List[Tuple[float, int]] = []  # (end_us, idx) of open ancestors
    for ts, dur, idx in sorted(events, key=lambda e: (e[0], -e[1])):
        end = ts + dur
        while stack and stack[-1][0] <= ts:
            stack.pop()
        if stack:
            p_end, p_idx = stack[-1]
            end = min(end, p_end)  # clip clock-jitter overhang to parent
            covered = end - ts
            if covered > 0:
                out[p_idx] = out.get(p_idx, 0.0) - covered
        dur = max(end - ts, 0.0)
        out[idx] = out.get(idx, 0.0) + dur
        stack.append((end, idx))
    return out


def _merge_intervals(ivs: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    if not ivs:
        return []
    ivs = sorted(ivs)
    out = [list(ivs[0])]
    for a, b in ivs[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _union_len(ivs: List[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in _merge_intervals(ivs))


def _intersect_len(
    a: List[Tuple[float, float]], b: List[Tuple[float, float]]
) -> float:
    a, b = _merge_intervals(a), _merge_intervals(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


# --------------------------------------------------------------------------
# Trace loading
# --------------------------------------------------------------------------


def find_traces(capture_dir: str) -> List[str]:
    """Every ``*.trace.json.gz`` under ``capture_dir`` (JAX writes
    ``plugins/profile/<run>/<host>.trace.json.gz``; multi-host captures
    and ``obs pod``-collected trees nest one layout per host — the walk
    finds them all). Sorted for deterministic reports."""
    out: List[str] = []
    for root, _dirs, files in os.walk(capture_dir):
        for f in files:
            if f.endswith(".trace.json.gz"):
                out.append(os.path.join(root, f))
    return sorted(out)


def load_trace(path: str) -> List[dict]:
    """The ``traceEvents`` list of one trace file (``.json`` or
    ``.json.gz``). Raises :class:`MalformedTraceError` on a truncated
    gzip or torn/invalid JSON — typed, so the auto-analyze hook can count
    the drop instead of dying."""
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rt", encoding="utf-8", errors="replace") as f:
                data = json.load(f)
        else:
            with open(path, encoding="utf-8", errors="replace") as f:
                data = json.load(f)
    except (OSError, EOFError, gzip.BadGzipFile) as e:
        raise MalformedTraceError(
            f"{path}: unreadable trace (truncated gzip?): {e}"
        ) from e
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise MalformedTraceError(
            f"{path}: torn/invalid trace JSON: {e}"
        ) from e
    if isinstance(data, list):  # bare event-array form of the spec
        return [e for e in data if isinstance(e, dict)]
    if isinstance(data, dict) and isinstance(data.get("traceEvents"), list):
        return [e for e in data["traceEvents"] if isinstance(e, dict)]
    raise MalformedTraceError(f"{path}: no traceEvents array")


# --------------------------------------------------------------------------
# Per-trace analysis
# --------------------------------------------------------------------------


def _track_selector(
    events: List[dict],
) -> Tuple[set, set]:
    """``(device_op_tids, host_pids)`` — the attribution universe.

    Real accelerator captures carry ``/device:*`` processes; their
    ``XLA Ops`` line holds the op executions (other device threads are
    alternate VIEWS of the same time — summing them would double-count),
    so when any exists, those threads are the universe and every event
    on them counts. CPU-emulation captures have no device process; XLA
    op executions are scattered across the ``/host:*`` process's thread
    pools (``tf_XLAEigen``, the TFRT client dispatch threads, even the
    calling ``python`` thread for inlined ops), so selection there is by
    CONTENT instead: events stamped with ``args.hlo_op``/``hlo_module``
    count, runtime bookkeeping (``start_trace``, ``ExecuteHelper``,
    threadpool markers) does not."""
    pid_name: Dict[object, str] = {}
    tid_name: Dict[Tuple[object, object], str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        args = e.get("args") or {}
        if e.get("name") == "process_name":
            pid_name[e.get("pid")] = str(args.get("name", ""))
        elif e.get("name") == "thread_name":
            tid_name[(e.get("pid"), e.get("tid"))] = str(args.get("name", ""))
    device_pids = {p for p, n in pid_name.items() if n.startswith("/device:")}
    if device_pids:
        ops = {
            k for k, n in tid_name.items()
            if k[0] in device_pids and n.startswith("XLA Ops")
        }
        if ops:
            return ops, set()
        # no "XLA Ops" line (GPU stream threads, older layouts): every
        # thread of the device processes
        return {k for k in tid_name if k[0] in device_pids}, set()
    return set(), {p for p, n in pid_name.items() if n.startswith("/host:")}


def _is_hlo_event(e: dict) -> bool:
    args = e.get("args")
    return isinstance(args, dict) and (
        "hlo_op" in args or "hlo_module" in args
    )


def analyze_events(events: List[dict]) -> dict:
    """Attribution over one trace's event list. Raises
    :class:`NoDeviceTrackError` when no device/XLA-op events exist."""
    device_tids, host_pids = _track_selector(events)
    # complete events per op thread: (ts, dur, index into flat lists)
    per_thread: Dict[Tuple[object, object], List[Tuple[float, float, int]]] = {}
    names: List[str] = []
    cats: List[str] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        key = (e.get("pid"), e.get("tid"))
        if device_tids:
            if key not in device_tids:
                continue
        elif not (key[0] in host_pids and _is_hlo_event(e)):
            continue
        ts, dur = e.get("ts"), e.get("dur")
        if not isinstance(ts, (int, float)) or not isinstance(dur, (int, float)):
            continue
        name = str(e.get("name", ""))
        idx = len(names)
        names.append(name)
        cats.append(classify(name))
        per_thread.setdefault(key, []).append((float(ts), float(dur), idx))
    if not per_thread:
        raise NoDeviceTrackError(
            "no device track: the trace has no /device:* 'XLA Ops' thread "
            "and no /host:* XLA op events (args.hlo_op) to attribute"
        )
    cat_us = {c: 0.0 for c in CATEGORIES}
    coll_us: Dict[str, float] = {}
    infeed_us = 0.0
    op_self_us: Dict[str, float] = {}
    op_count: Dict[str, int] = {}
    comm_ivs: List[Tuple[float, float]] = []
    compute_ivs: List[Tuple[float, float]] = []
    busy_us = 0.0
    for evs in per_thread.values():
        selfs = _self_times_us(evs)
        for ts, dur, idx in evs:
            s = selfs.get(idx, 0.0)
            cat = cats[idx]
            cat_us[cat] += s
            busy_us += s
            if cat == "collective":
                kind = collective_kind(names[idx]) or "other"
                coll_us[kind] = coll_us.get(kind, 0.0) + s
                comm_ivs.append((ts, ts + dur))
            elif cat in ("matmul_conv", "fusion_other"):
                compute_ivs.append((ts, ts + dur))
            if cat == "infeed_outfeed" and op_stem(names[idx]).startswith("infeed"):
                infeed_us += s
            if cat != "host":
                op_self_us[names[idx]] = op_self_us.get(names[idx], 0.0) + s
                op_count[names[idx]] = op_count.get(names[idx], 0) + 1
    comm_us = _union_len(comm_ivs)
    overlapped_us = _intersect_len(comm_ivs, compute_ivs)
    sec = 1e-6
    return {
        "op_threads": len(per_thread),
        "n_op_events": len(names),
        "device_busy_s": busy_us * sec,
        "categories": {c: cat_us[c] * sec for c in CATEGORIES},
        "collectives": {
            k: v * sec for k, v in sorted(coll_us.items())
        },
        "infeed_stall_s": infeed_us * sec,
        "overlap": {
            "comm_s": comm_us * sec,
            "compute_s": _union_len(compute_ivs) * sec,
            "overlapped_s": overlapped_us * sec,
            "overlap_frac": (
                round(overlapped_us / comm_us, 4) if comm_us > 0 else None
            ),
        },
        "_op_self_s": {n: v * sec for n, v in op_self_us.items()},
        "_op_count": op_count,
    }


# --------------------------------------------------------------------------
# Capture-level analysis (the public entry points)
# --------------------------------------------------------------------------


def _top_ops(
    self_s: Dict[str, float], count: Dict[str, int], k: int
) -> List[dict]:
    return [
        {
            "name": n,
            "category": classify(n),
            "self_s": round(s, 6),
            "count": count.get(n, 0),
        }
        for n, s in sorted(self_s.items(), key=lambda kv: -kv[1])[:k]
    ]


def _merge_trace(total: dict, tr: dict) -> None:
    total["device_busy_s"] += tr["device_busy_s"]
    for c in CATEGORIES:
        total["categories"][c] += tr["categories"][c]
    for kind, s in tr["collectives"].items():
        total["collectives"][kind] = total["collectives"].get(kind, 0.0) + s
    total["infeed_stall_s"] += tr["infeed_stall_s"]
    for f in ("comm_s", "compute_s", "overlapped_s"):
        total["overlap"][f] += tr["overlap"][f]
    for n, s in tr["_op_self_s"].items():
        total["_op_self_s"][n] = total["_op_self_s"].get(n, 0.0) + s
    for n, c in tr["_op_count"].items():
        total["_op_count"][n] = total["_op_count"].get(n, 0) + c


def _finish(total: dict, top_k: int) -> dict:
    comm = total["overlap"]["comm_s"]
    total["overlap"]["overlap_frac"] = (
        round(total["overlap"]["overlapped_s"] / comm, 4) if comm > 0 else None
    )
    for f in ("comm_s", "compute_s", "overlapped_s"):
        total["overlap"][f] = round(total["overlap"][f], 6)
    busy = total["device_busy_s"]
    total["collective_frac"] = (
        round(total["categories"]["collective"] / busy, 4) if busy > 0 else None
    )
    total["top_ops"] = _top_ops(
        total.pop("_op_self_s"), total.pop("_op_count"), top_k
    )
    total["categories"] = {
        c: round(v, 6) for c, v in total["categories"].items()
    }
    # the reported busy is the sum of the ROUNDED categories, so the
    # sum-to-busy invariant survives the 6-decimal rounding exactly
    total["device_busy_s"] = round(sum(total["categories"].values()), 6)
    total["collectives"] = {
        k: round(v, 6) for k, v in sorted(total["collectives"].items())
    }
    total["infeed_stall_s"] = round(total["infeed_stall_s"], 6)
    return total


def _fresh_total() -> dict:
    return {
        "device_busy_s": 0.0,
        "categories": {c: 0.0 for c in CATEGORIES},
        "collectives": {},
        "infeed_stall_s": 0.0,
        "overlap": {"comm_s": 0.0, "compute_s": 0.0, "overlapped_s": 0.0},
        "_op_self_s": {},
        "_op_count": {},
    }


def analyze_capture(capture_dir: str, top_k: int = 10) -> dict:
    """The attribution report over every trace file under a capture
    directory (one per host in a multi-host capture — their device times
    sum; the overlap fraction is the ratio of summed overlapped to summed
    comm seconds).

    Per-file failures (truncated gzip, torn JSON, no device track) become
    counted entries in ``report["dropped"]`` + ``report["errors"]`` — a
    PARTIAL report, never an exception — as long as at least one trace
    analyzes. With nothing analyzable the capture is useless and a typed
    :class:`CaptureError` subclass says why (empty dir vs all-malformed
    vs no-device-track)."""
    if not os.path.isdir(capture_dir):
        raise EmptyCaptureError(f"{capture_dir}: not a directory")
    paths = find_traces(capture_dir)
    if not paths:
        raise EmptyCaptureError(
            f"{capture_dir}: no *.trace.json.gz under it — the capture "
            "wrote nothing (profiler backend unavailable, or the dir is "
            "not a jax.profiler output)"
        )
    total = _fresh_total()
    traces: List[dict] = []
    errors: List[dict] = []
    dropped = {"malformed_trace": 0, "no_device_track": 0}
    for path in paths:
        try:
            tr = analyze_events(load_trace(path))
        except CaptureError as e:
            dropped[e.kind] = dropped.get(e.kind, 0) + 1
            errors.append({"path": path, "kind": e.kind, "error": str(e)[:300]})
            continue
        _merge_trace(total, tr)
        traces.append({
            "path": path,
            "op_threads": tr["op_threads"],
            "n_op_events": tr["n_op_events"],
            "device_busy_s": round(tr["device_busy_s"], 6),
        })
    if not traces:
        kinds = {e["kind"] for e in errors}
        cls = (
            NoDeviceTrackError if kinds == {"no_device_track"}
            else MalformedTraceError
        )
        raise cls(
            f"{capture_dir}: none of {len(paths)} trace file(s) analyzable "
            f"({'; '.join(e['error'] for e in errors[:3])})"
        )
    report = _finish(total, top_k)
    report.update({
        "capture_dir": capture_dir,
        "n_traces": len(paths),
        "analyzed": len(traces),
        "traces": traces,
        "dropped": {k: v for k, v in dropped.items() if v},
        "errors": errors,
    })
    return report


def analyze_trace_file(path: str, top_k: int = 10) -> dict:
    """Analyze ONE Chrome trace file (``.json`` or ``.json.gz``) — the
    offline path for a trace pulled out of a capture by hand. (The
    merged timeline ``obs pod --trace-out`` writes holds HOST spans,
    not XLA op events — it has no device track to attribute, so it
    raises :class:`NoDeviceTrackError` by design; pod-collected CAPTURE
    trees — per-host ``plugins/profile`` layouts under one root — go
    through :func:`analyze_capture`, whose walk finds them all.)"""
    total = _fresh_total()
    _merge_trace(total, analyze_events(load_trace(path)))
    report = _finish(total, top_k)
    report.update({
        "capture_dir": path, "n_traces": 1, "analyzed": 1,
        "traces": [{"path": path}], "dropped": {}, "errors": [],
    })
    return report


# --------------------------------------------------------------------------
# Report shaping — the compact record + the rank-0 line
# --------------------------------------------------------------------------


def compact(report: dict, top_k: int = 3) -> dict:
    """The history-record payload (``profile_analysis``, schema v6): the
    category split, overlap, collective share, and the top few ops —
    small enough to stamp per capture without bloating the JSONL."""
    out = {
        "device_busy_s": report["device_busy_s"],
        "categories": dict(report["categories"]),
        "collectives": dict(report["collectives"]),
        "collective_frac": report.get("collective_frac"),
        "overlap_frac": report["overlap"]["overlap_frac"],
        "comm_s": report["overlap"]["comm_s"],
        "infeed_stall_s": report["infeed_stall_s"],
        "top_ops": [
            {"name": o["name"], "self_s": o["self_s"]}
            for o in report.get("top_ops", [])[:top_k]
        ],
        "analyzed_traces": report.get("analyzed", 1),
    }
    if report.get("dropped"):
        out["dropped"] = dict(report["dropped"])
    return out


def summary_line(report: dict) -> str:
    """One rank-0 line of attribution per capture — the answer a capture
    exists to give, without opening Perfetto. Accepts both the full
    report and the :func:`compact` record shape."""
    busy = report.get("device_busy_s") or 0.0
    cats = report.get("categories") or {}

    def pct(c):
        v = cats.get(c, 0.0)
        return f"{v / busy:.0%}" if busy > 0 else "-"

    colls = report.get("collectives") or {}
    coll_detail = (
        " (" + ", ".join(f"{k} {v:.3f}s" for k, v in colls.items()) + ")"
        if colls else ""
    )
    ov = (report.get("overlap") or {}).get(
        "overlap_frac", report.get("overlap_frac")
    )
    parts = [
        f"device busy {busy:.3f}s:",
        f"matmul/conv {pct('matmul_conv')},",
        f"collectives {pct('collective')}{coll_detail},",
        f"infeed/outfeed {pct('infeed_outfeed')},",
        f"fusion/other {pct('fusion_other')},",
        f"host {pct('host')};",
        f"comm/compute overlap {ov:.0%};" if isinstance(ov, (int, float))
        else "comm/compute overlap -;",
        f"infeed stall {report.get('infeed_stall_s', 0.0):.3f}s",
    ]
    if report.get("dropped"):
        n = sum(report["dropped"].values())
        parts.append(f"({n} trace file(s) dropped)")
    return " ".join(parts)


def format_text(report: dict) -> str:
    """Full human rendering for the ``obs xprof`` CLI."""
    lines = [
        f"capture {report.get('capture_dir')}: "
        f"{report.get('analyzed')}/{report.get('n_traces')} trace file(s) "
        f"analyzed"
    ]
    for e in report.get("errors", []):
        lines.append(f"  DROPPED [{e['kind']}] {e['error']}")
    busy = report["device_busy_s"]
    lines.append(f"device busy: {busy:.6f}s across "
                 f"{sum(t.get('op_threads', 0) for t in report.get('traces', []))} "
                 "op thread(s)")
    lines.append(f"{'category':>16} {'seconds':>12} {'share':>7}")
    for c in CATEGORIES:
        v = report["categories"][c]
        share = f"{v / busy:.1%}" if busy > 0 else "-"
        lines.append(f"{c:>16} {v:>12.6f} {share:>7}")
    if report.get("collectives"):
        lines.append("collectives by kind:")
        for k, v in report["collectives"].items():
            lines.append(f"{k:>16} {v:>12.6f}")
    ov = report["overlap"]
    frac = ov.get("overlap_frac")
    lines.append(
        f"comm/compute overlap: "
        + (f"{frac:.1%}" if isinstance(frac, (int, float)) else "-")
        + f" ({ov['overlapped_s']:.6f}s of {ov['comm_s']:.6f}s comm "
        f"overlapped with {ov['compute_s']:.6f}s compute)"
    )
    lines.append(f"infeed stall: {report['infeed_stall_s']:.6f}s")
    if report.get("top_ops"):
        lines.append("top ops by self time:")
        for o in report["top_ops"]:
            lines.append(
                f"  {o['self_s']:>10.6f}s  {o['name']}  "
                f"[{o['category']}] ×{o['count']}"
            )
    return "\n".join(lines)
