"""Device time by the program's named scopes.

The program wraps its kernel-like regions in ``jax.named_scope``
(``ssm/scan``, ``moe/experts``, ``attn/causal``, ...). A v5e capture names an
op event by its HLO text and carries no stat with the op's ``op_name`` (read
off this cell's first capture, PR 33), so the trace alone cannot tell the
scopes apart; the program keeps the join, ``tpu_dist/obs/hlo_scopes.py``:
the instruction names of its compiled step by scope. The readers that need a
region's time come here: the self time (a ``while`` spans its body's ops) of
the first chip's ops inside the traced window, summed over the ops the
program names for the scope.

Where the program has no such table or no such scope (the parent of the PR
that added one), the time is None and the reader reports nothing.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

from benchmarks.harness import trace as trace_lib


def op_events(capture_dir: str) -> Optional[List[Tuple[float, float, str]]]:
    """``(start_ns, end_ns, instruction name)`` of every event on the first
    chip's op line."""
    path = trace_lib.find_xplane(capture_dir)
    if path is None:
        return None
    planes = trace_lib.device_planes(trace_lib.load_xplane(path))
    if not planes:
        return None
    names: Dict[str, str] = {}
    out = []
    for text, start, dur in trace_lib._line(planes[0], trace_lib.OP_LINE):
        op = names.get(text) or names.setdefault(text, trace_lib.parse_op(text)["op"])
        out.append((start, start + dur, op))
    return out or None


def seconds_in(events, window_ns, ops: Iterable[str]) -> float:
    """Self time inside ``window_ns`` of the events named in ``ops``, seconds."""
    lo, hi = window_ns
    ops = frozenset(ops)
    inside = [(max(s, lo), min(e, hi), op) for s, e, op in events if e > lo and s < hi]
    selfs = trace_lib.self_times([(s, e) for s, e, _ in inside])
    return sum(t for (_, _, op), t in zip(inside, selfs) if op in ops) * 1e-9


def program_ops(scope: str) -> frozenset:
    """The compiled step's instructions under ``scope``, as the program
    recorded them; empty where it records none."""
    try:
        from tpu_dist.obs import hlo_scopes  # noqa: PLC0415
    except ImportError:
        return frozenset()
    return hlo_scopes.ops_in(scope)


def scope_seconds(window: Dict[str, Any], scope: str) -> Optional[float]:
    """Seconds the first chip spent in ops of ``scope`` during the traced
    epoch of this run, or None. The capture is read once a run and kept in
    ``window``."""
    ops = program_ops(scope)
    if not ops:
        return None
    if "_scope_events" not in window:
        cell = window["cell"]
        capture_dir = os.path.join(cell.root, "chiprun_out", "trace", cell.name)
        events, span = None, None
        try:
            events = op_events(capture_dir)
            with open(os.path.join(capture_dir, "program.json"), encoding="utf-8") as f:
                host = json.load(f).get("host")
            span = tuple(host["window"]) if host else None
        except (OSError, ValueError, KeyError):
            pass
        window["_scope_events"] = (events, span)
    events, span = window["_scope_events"]
    if not events:
        return None
    if span is None:
        span = (min(s for s, _, _ in events), max(e for _, e, _ in events))
    return seconds_in(events, span, ops) or None


def roofline_share(window: Dict[str, Any], scope: str, ops: float, nbytes: float) -> Optional[float]:
    """100 x the least time the chip could take for ``ops`` operations and
    ``nbytes`` bytes (the larger of the two bounds, ``peaks.json``) over the
    traced time of ``scope``; says which bound it is on an earlier line."""
    t = scope_seconds(window, scope)
    if not t:
        return None
    peaks = window["peaks"]
    t_ops = ops / float(peaks["bf16_flops_per_s"])
    t_mem = nbytes / float(peaks["hbm_bytes_per_s"])
    steps = window["traced_epoch"]["steps"]
    window["say"](
        f"{scope}: {1e3 * t / steps:.2f} ms a step in its ops; at the peaks its operations need "
        f"{1e3 * t_ops / steps:.2f} ms and its bytes {1e3 * t_mem / steps:.2f} ms a step "
        f"({'compute' if t_ops >= t_mem else 'memory'}-bound)"
    )
    return 100.0 * max(t_ops, t_mem) / t


def model_file(window: Dict[str, Any]):
    """The cell's ``benchmarks/models/<reference>.py``."""
    from benchmarks.harness import manifest  # noqa: PLC0415

    cell = window["cell"]
    return manifest.load_module(cell.root, "models", cell.config["reference"])
