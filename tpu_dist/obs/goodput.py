"""Goodput ledger — where did this run's wall-clock actually go?
(``docs/observability.md``.)

Production TPU fleets budget in *goodput*: of every wall-clock second a
job consumed — compiles, checkpoint I/O, input stalls, evals, preemptions
and the restarts after them — what fraction was productive training?
PRs 4-5 instrumented each ingredient (spans, counters, step phases,
compile seconds) but never closed the books. This module does: it
partitions the run's wall-clock, **from Trainer construction through
exit, resumed segments of the same logical run included**, into named
buckets that sum to the elapsed time by construction.

Buckets (field ``<name>_s`` in every record):

* ``productive`` — steady-state step-loop time (dispatch + the in-loop
  host work that paces it); the goodput numerator,
* ``compile`` — XLA backend-compile wall time (the ``compile.seconds``
  counter fed by the ``jax.monitoring`` listener),
* ``ckpt`` — checkpoint save/restore, the restore ladder included,
* ``data_stall`` — blocking in the loader iterator (the step-phase
  ``data_wait`` the trainer already measures),
* ``eval`` — validation,
* ``preempt`` — preemption/restart loss: the SIGTERM-to-exit tail in the
  dying process plus (offline) the wall-clock gap between a segment's
  last record and the resumed segment's construction,
* ``preempt_for_serve`` — the fleet arbiter took this run's chips for a
  breached serving SLO: a world-change gap whose resume record carries
  a propagated ``decision_id`` with cause ``serve_breach`` (schema
  v15). Split out of ``recovery`` so "we chose to pay this for the
  SLO" and "elastic kept us alive" are budgeted separately,
* ``recovery`` — divergence auto-recovery (restore + LR backoff), plus
  (offline) the relaunch gap of any OTHER elastic resize,
* ``unattributed`` — whatever remains; never hidden, so a growing
  remainder is itself a finding.

Two halves share the bucket set:

* **Live** (:class:`GoodputLedger`) — the Trainer attributes seconds as
  they happen and emits one ``goodput`` history record per epoch window
  plus a run-end totals record (schema v4, additive) and a rank-0 ledger
  line. Windows chain: each record's ``window_s`` runs from the previous
  record to this one, so the records partition the run exactly.
* **Offline** (:func:`run_ledger`) — fold a ``--log_file`` JSONL
  (possibly holding several resumed segments) back into one run-level
  ledger; ``obs summarize`` prints it and ``obs compare --goodput``
  gates on its ``goodput_frac``.

Stdlib-only on purpose: the offline half must run anywhere the log can
be copied to, and the live half is pure host arithmetic (the TD106
telemetry contract covers it — nothing here touches the traced step).
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Tuple

#: Attributable buckets, in report order. ``unattributed`` is derived
#: (window minus the rest), never written to directly.
BUCKETS: Tuple[str, ...] = (
    "productive", "compile", "ckpt", "data_stall", "eval",
    "preempt", "preempt_for_serve", "recovery",
)
ALL_BUCKETS: Tuple[str, ...] = BUCKETS + ("unattributed",)


class GoodputLedger:
    """Live wall-clock bookkeeping for one process's run.

    The clock origin is the Trainer's construction instant; every
    attribution is host arithmetic on ``time.monotonic`` readings.
    ``window_record()`` closes the current window (everything since the
    previous record), deriving ``unattributed`` as the unexplained
    remainder, and folds it into the run totals — so the per-window
    records partition ``[t0, now]`` exactly and the invariant *bucket
    sum equals elapsed wall-clock* holds by construction.
    """

    def __init__(self, t0: Optional[float] = None):
        self.t0 = t0 if t0 is not None else time.monotonic()
        self._mark = self.t0
        self._window: Dict[str, float] = {b: 0.0 for b in BUCKETS}
        self._totals: Dict[str, float] = {b: 0.0 for b in ALL_BUCKETS}

    def add(self, bucket: str, seconds: float) -> None:
        """Attribute ``seconds`` of the current window to ``bucket``.
        Negative inputs (clock weirdness) clamp to zero rather than
        corrupt the invariant."""
        if bucket not in self._window:
            raise ValueError(f"unknown goodput bucket {bucket!r}; have {BUCKETS}")
        if seconds > 0:
            self._window[bucket] += float(seconds)

    @contextlib.contextmanager
    def timed(self, bucket: str):
        """Attribute a region's wall time to ``bucket`` (exception-safe:
        a failing checkpoint write still spent the seconds)."""
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.add(bucket, time.monotonic() - t0)

    def window_value(self, bucket: str) -> float:
        """Seconds attributed to ``bucket`` in the OPEN window — lets the
        trainer subtract e.g. mid-epoch ckpt time out of an epoch's
        productive remainder."""
        return self._window[bucket]

    def window_record(self, now: Optional[float] = None) -> Dict[str, float]:
        """Close the current window: per-bucket seconds + ``window_s`` +
        the derived ``unattributed_s``; folds into the run totals and
        starts the next window at ``now``."""
        now = time.monotonic() if now is None else now
        window_s = max(now - self._mark, 0.0)
        attributed = sum(self._window.values())
        # over-attribution (overlapping regions double-counted) would push
        # the remainder negative; clamp and let the buckets overshoot the
        # window visibly rather than silently rescale them
        unattributed = max(window_s - attributed, 0.0)
        rec = {f"{b}_s": round(self._window[b], 4) for b in BUCKETS}
        rec["unattributed_s"] = round(unattributed, 4)
        rec["window_s"] = round(window_s, 4)
        for b in BUCKETS:
            self._totals[b] += self._window[b]
            self._window[b] = 0.0
        self._totals["unattributed"] += unattributed
        self._mark = now
        return rec

    def run_totals(self, now: Optional[float] = None) -> Dict[str, float]:
        """Whole-run ledger: per-bucket totals over every CLOSED window,
        total elapsed, and ``goodput_frac``. Call :meth:`window_record`
        first to fold the open tail in."""
        now = time.monotonic() if now is None else now
        elapsed = max(self._mark - self.t0, 0.0)
        out = {f"{b}_s": round(self._totals[b], 4) for b in ALL_BUCKETS}
        out["elapsed_s"] = round(elapsed, 4)
        out["goodput_frac"] = round(
            self._totals["productive"] / elapsed, 4
        ) if elapsed > 0 else 0.0
        return out


def resume_direction(rec: dict) -> Optional[str]:
    """Classify a ``resume`` record's elastic direction — ONE home for
    the ``prev_dp``/``dp`` comparison every consumer renders or charges
    by (this ledger, ``summarize``, ``tail``, ``pod``):

    * ``'grown'`` — the world got BIGGER (scale-up / fleet receipt),
    * ``'resharded'`` — any other elastic resize: a shrink, or a
      same-size restore whose dp-dependent leaves were re-laid,
    * ``None`` — a plain same-world resume (no elastic resize at all).
    """
    prev_dp, dp = rec.get("prev_dp"), rec.get("dp")
    ints = isinstance(prev_dp, int) and isinstance(dp, int)
    if ints and dp > prev_dp:
        return "grown"
    if rec.get("resharded") or (ints and dp != prev_dp):
        return "resharded"
    return None


def fleet_move_phrase(rec: dict) -> str:
    """The "who → whom" phrase of a ``fleet`` decision record — ONE home
    for the three renderers (``summarize``, ``tail``, ``pod``). Handles
    a grant (no donor: chips from the free pool), a donation (no
    recipient: chips bank as pending for ``for_run``), and the paired
    form foreign tooling may still write."""
    donor, recipient = rec.get("donor"), rec.get("recipient")
    if donor and recipient:
        phrase = f"{donor} -> {recipient}"
    elif recipient:
        phrase = f"free pool -> {recipient}"
    elif donor:
        phrase = f"{donor} -> pending pool"
        if rec.get("for_run"):
            phrase += f" (toward {rec['for_run']})"
    else:
        phrase = "?"
    phrase += f" ({rec.get('chips')} chip(s))"
    if rec.get("preempt"):
        # an SLO-breach preemption (multi-tenant pod): the move was
        # demanded by a serving breach, not offered by a stalled donor
        phrase += " [SLO preemption]"
    if rec.get("decision_id") is not None:
        # causal arbitration tracing (schema v15): every renderer names
        # the arbitration, so a donate and its completion grant read as
        # one chain at a glance
        phrase += f" [decision #{rec['decision_id']}]"
    return phrase


# -- offline: fold a run's JSONL records back into one ledger ---------------


def _zero_totals() -> Dict[str, float]:
    out = {f"{b}_s": 0.0 for b in ALL_BUCKETS}
    out["elapsed_s"] = 0.0
    return out


def run_ledger(records: List[dict]) -> Optional[dict]:
    """Fold a history's ``goodput`` records — across resumed segments —
    into one run-level ledger.

    Segments are delimited the way ``summarize`` delimits them: a
    ``run_id`` change mid-file is a restart (same logical run, fresh
    process). Within a segment the run-end totals record (``final: true``)
    is authoritative; a segment that died before writing one (preemption,
    crash) is reconstructed by summing its window records. The wall-clock
    gap between a segment's LAST record and the next segment's
    construction instant (its first record's ``ts - rel_s``) is the
    restart loss nobody inside either process could see — it lands in
    ``preempt_s``, except when the new segment opens with an ELASTIC
    ``resume`` record: one flagged resharded, or one whose world size
    changed (``prev_dp != dp`` — a probe-triggered grow or a
    scheduler-initiated donation can re-lay zero leaves when the padded
    lengths happen to agree, and a voluntary resize must never inflate
    ``preempt_s``). That gap is the reshard/resize+relaunch cost of
    keeping the run alive at a new world size and is charged to
    ``recovery_s`` — UNLESS the resume carries a propagated
    ``decision_id`` with ``decision_cause == "serve_breach"`` (schema
    v15: the fleet arbiter preempted this run for a breached serving
    SLO), in which case it is charged to ``preempt_for_serve_s``: the
    pod CHOSE to pay that gap for the SLO, and budgeting it as generic
    elastic recovery would hide the cost of the co-scheduling policy
    (docs/resilience.md "Elastic training" / "Scale-up & fleet
    scheduling"). The partition invariant is untouched: all three gap
    accumulators land in ``restart_gap_s`` and ``elapsed_s``, so the
    buckets still sum to wall-clock exactly. Returns None when the log
    holds no goodput records (an old-schema log)."""
    totals = _zero_totals()
    n_segments = 0
    saw_goodput = False
    cur_run = object()
    seg_final: Optional[dict] = None
    seg_windows = _zero_totals()
    seg_has_window = False
    last_ts: Optional[float] = None
    restart_s = 0.0
    reshard_gap_s = 0.0
    serve_gap_s = 0.0

    def fold_segment():
        nonlocal seg_final, seg_windows, seg_has_window
        src = None
        if seg_final is not None:
            src = seg_final
        elif seg_has_window:
            src = seg_windows
        if src is not None:
            for b in ALL_BUCKETS:
                totals[f"{b}_s"] += float(src.get(f"{b}_s", 0.0) or 0.0)
            totals["elapsed_s"] += float(src.get("elapsed_s", 0.0) or 0.0)
        seg_final, seg_windows, seg_has_window = None, _zero_totals(), False

    for rec in records:
        rid = rec.get("run_id")
        if n_segments == 0:
            cur_run = rid
            n_segments = 1
        elif rid is not None and rid != cur_run:
            # a NON-None run_id change is a restart (same rule summarize
            # uses for its counter-delta resets); id-less records — old
            # schemas, foreign lines — never split a segment
            fold_segment()
            # restart gap: previous segment's last visible instant to
            # this segment's construction (ts minus its rel_s offset).
            # A segment whose boundary record is a resharded 'resume'
            # came back at a NEW world size — its gap is elastic
            # recovery, not preemption loss
            ts, rel = rec.get("ts"), rec.get("rel_s")
            if (
                last_ts is not None
                and isinstance(ts, (int, float))
                and isinstance(rel, (int, float))
            ):
                gap = max(float(ts) - float(rel) - last_ts, 0.0)
                if (
                    rec.get("kind") == "resume"
                    and resume_direction(rec) is not None
                ):
                    if (
                        rec.get("decision_cause") == "serve_breach"
                        and rec.get("decision_id") is not None
                    ):
                        # the fleet arbiter took the chips for a
                        # breached serving SLO (the relaunch env
                        # propagated its decision_id here) — this gap
                        # is the chosen cost of the co-scheduling
                        # policy, not generic elastic recovery
                        serve_gap_s += gap
                    else:
                        reshard_gap_s += gap
                else:
                    restart_s += gap
            cur_run = rid
            n_segments += 1
        if isinstance(rec.get("ts"), (int, float)):
            last_ts = float(rec["ts"])
        if rec.get("kind") != "goodput":
            continue
        saw_goodput = True
        if rec.get("final"):
            seg_final = rec
        else:
            seg_has_window = True
            for b in ALL_BUCKETS:
                seg_windows[f"{b}_s"] += float(rec.get(f"{b}_s", 0.0) or 0.0)
            seg_windows["elapsed_s"] += float(rec.get("window_s", 0.0) or 0.0)
    fold_segment()
    if not saw_goodput:
        return None
    totals["preempt_s"] = round(totals["preempt_s"] + restart_s, 4)
    totals["preempt_for_serve_s"] = round(
        totals["preempt_for_serve_s"] + serve_gap_s, 4
    )
    totals["recovery_s"] = round(totals["recovery_s"] + reshard_gap_s, 4)
    totals["restart_gap_s"] = round(
        restart_s + reshard_gap_s + serve_gap_s, 4
    )
    totals["elapsed_s"] = round(
        totals["elapsed_s"] + restart_s + reshard_gap_s + serve_gap_s, 4
    )
    for b in ALL_BUCKETS:
        totals[f"{b}_s"] = round(totals[f"{b}_s"], 4)
    totals["n_segments"] = n_segments
    totals["goodput_frac"] = round(
        totals["productive_s"] / totals["elapsed_s"], 4
    ) if totals["elapsed_s"] > 0 else 0.0
    return totals


def ledger_line(totals: dict) -> str:
    """One-line rank-0 rendering of a run ledger (live or offline)."""
    parts = []
    for b in ALL_BUCKETS:
        v = totals.get(f"{b}_s", 0.0) or 0.0
        if v:
            parts.append(f"{b} {v:.1f}s")
    frac = totals.get("goodput_frac")
    return (
        f"goodput: {frac:.1%} of {totals.get('elapsed_s', 0.0):.1f}s "
        "wall-clock productive"
        + (f" ({', '.join(parts)})" if parts else "")
        + (
            f" across {totals['n_segments']} segment(s)"
            if totals.get("n_segments", 1) > 1 else ""
        )
    )
