"""Device time by the phases of the train step.

The program opens ``step/loss_grad``, ``step/grad_reduce``, ``step/optimizer``,
``step/metrics`` and ``data/*`` around the parts of its step and a scope around
every block of its models, and classifies each instruction of its compiled
step by ``op_name`` (``tpu_dist/obs/hlo_scopes.py``: ``phase_of`` has the
forms; forward, backward and recompute are told apart inside
``step/loss_grad``). Here the first chip's self time inside the traced window
(``harness/scopes.py``: a ``while`` spans its body's ops and counts only what
they leave) is summed an instruction, then a phase. Self times partition the
busy time, so the phases and ``other`` (instructions the program names under
no phase, and XLA's own, which carry no name) add up to ``device_step_ms`` x
the traced steps.

A fusion counts under the one ``op_name`` it carries: a weight gradient that
XLA fused with its SGD update reads as ``backward`` on the v5e (the fusion is
named after the convolution), and the update inside it is not ``optimizer``'s.

Where the program has no phase table (the parent of the PR that added it), or
its table was made from another tree's names (``hlo_scopes.missing`` not 0),
there is nothing to read and every reader here reports nothing.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.harness import scopes
from benchmarks.harness import trace as trace_lib

NAMED = ("forward", "backward", "recompute", "optimizer", "grad_reduce", "metrics", "data")


def table():
    """The program's ``hlo_scopes`` where it holds a phase table to trust."""
    try:
        from tpu_dist.obs import hlo_scopes  # noqa: PLC0415
    except ImportError:
        return None
    if not hasattr(hlo_scopes, "ops_in_phase"):
        return None
    if not hlo_scopes.has_phases() or hlo_scopes.missing():
        return None
    return hlo_scopes


def self_seconds(window: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Seconds of the first chip's self time inside the traced window, by
    instruction name; read once a run and kept in ``window``."""
    if "_op_self_s" not in window:
        out: Optional[Dict[str, float]] = None
        # fills window["_scope_events"] (the capture is read once a run)
        if scopes.scope_seconds(window, "") is not None:
            events, span = window["_scope_events"]
            lo, hi = span or (min(s for s, _, _ in events), max(e for _, e, _ in events))
            inside = [(max(s, lo), min(e, hi), op) for s, e, op in events if e > lo and s < hi]
            out = {}
            for (_, _, op), t in zip(inside, trace_lib.self_times([(s, e) for s, e, _ in inside])):
                out[op] = out.get(op, 0.0) + t * 1e-9
        window["_op_self_s"] = out
    return window["_op_self_s"]


def split(window: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Seconds by phase, ``other`` and their sum ``busy``; None where there
    is no table or no capture."""
    if "_phase_split" not in window:
        hlo_scopes = table()
        by_op = self_seconds(window) if hlo_scopes is not None else None
        out = None
        if by_op:
            out = {
                phase: sum(by_op.get(op, 0.0) for op in hlo_scopes.ops_in_phase(phase))
                for phase in NAMED
            }
            out["busy"] = sum(by_op.values())
            out["other"] = out["busy"] - sum(out[phase] for phase in NAMED)
        window["_phase_split"] = out
    return window["_phase_split"]


def phase_ms(window: Dict[str, Any], phase: str) -> Optional[float]:
    """Milliseconds a traced step of ``phase``."""
    parts, steps = split(window), window["traced_epoch"]["steps"]
    return 1e3 * parts[phase] / steps if parts and steps else None


def say_partition(window: Dict[str, Any]) -> None:
    """One earlier line: every phase a step, and their sum beside the trace's
    own busy time."""
    parts, steps = split(window), window["traced_epoch"]["steps"]
    if not parts or not steps:
        return
    busy = window.get("trace", {}).get("chip0_busy_s")
    window["say"](
        "phases, ms a step: "
        + ", ".join(f"{k} {1e3 * parts[k] / steps:.3f}" for k in NAMED + ("other",))
        + f"; sum {1e3 * parts['busy'] / steps:.3f}"
        + ("" if busy is None else f" beside device_step_ms {1e3 * busy / steps:.3f}")
    )


def unscoped_share(window: Dict[str, Any]) -> Optional[float]:
    """100 x the self time of ops under no block's scope and in none of
    optimizer, grad_reduce, metrics, data, over the busy time; says the ten
    heaviest of them on an earlier line."""
    hlo_scopes = table()
    by_op = self_seconds(window) if hlo_scopes is not None else None
    if not by_op:
        return None
    placed = hlo_scopes.attributed_ops()
    rest = sorted(((t, op) for op, t in by_op.items() if op not in placed), reverse=True)
    steps = window["traced_epoch"]["steps"] or 1
    window["say"](
        "outside every scope, ms a step: "
        + "; ".join(f"{op} {1e3 * t / steps:.3f} [{hlo_scopes.name_of(op) or 'no op_name'}]"
                    for t, op in rest[:10])
    )
    return 100.0 * sum(t for t, _ in rest) / sum(by_op.values())
