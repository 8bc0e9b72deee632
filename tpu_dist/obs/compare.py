"""Run-compare regression gate — ``python -m tpu_dist.obs compare``.

Diffs two runs' telemetry and exits nonzero on a regression, so CI can
gate a change on measured training health instead of an eyeballed JSON
diff. Two input modes:

* **history mode** (default): both inputs are ``--log_file`` JSONLs; each
  is folded through :func:`tpu_dist.obs.summarize.summarize` and the
  comparison runs over the derived scalars — mean throughput, step-time
  p50/p95/p99, data-stall fraction, mean MFU, final train loss, final
  val top-1.
* **bench mode** (``--bench``): both inputs are ``bench.py`` output files
  (one JSON object per line, ``BENCH_*.json``); records are matched by
  their ``metric`` name and compared on throughput / step-time /
  sec-per-epoch / MFU.

A metric regresses when the candidate is worse than the baseline by more
than ``threshold`` (relative, default 5%) plus the metric's absolute
slack (noise floor — stall fraction and MFU move in absolute points on
quiet runs, a pure ratio would flag 0.1% vs 0.2% stall as a 2× blowup).
Better-than-baseline is never flagged, metrics missing from either side
are reported as skipped (never silently dropped), and a self-compare is
zero regressions by construction.

Pure host-side file crunching: no jax, runs anywhere the package imports.
All output formatting returns strings — printing (and the exit code)
belongs to ``obs/__main__.py``.
"""

from __future__ import annotations

import json
from typing import List, Optional, Tuple

from tpu_dist.obs import summarize as summ

#: ONE metric-direction registry: ``name -> (direction, absolute slack)``
#: for every scalar any compare mode gates on. Direction is which way is
#: BETTER (``lower`` = latency-style, ``higher`` = throughput-style);
#: slack is added to the relative allowance (noise floor — fractions
#: move in absolute points on quiet runs). The metric tables below
#: (history / bench / ``--slo``) all derive from this registry via
#: :func:`direction_of`, so a new latency or queue metric declares its
#: direction ONCE instead of hand-rolling it per comparison (the
#: overlap/collective special-casing of PR 8, generalized).
METRIC_DIRECTIONS: dict = {
    "images_per_sec_mean": ("higher", 0.0),
    "step_time_p50_s": ("lower", 0.0),
    "step_time_p95_s": ("lower", 0.0),
    "step_time_p99_s": ("lower", 0.0),
    "data_stall_frac": ("lower", 0.02),
    "mfu_mean": ("higher", 0.005),
    "final_loss": ("lower", 0.02),
    "final_val_top1": ("higher", 0.5),
    "goodput_frac": ("higher", 0.01),
    # capture-derived schedule health (obs/xprof.py, profile_analysis
    # records): mean comm/compute overlap — LOWER overlap means newly
    # serialized collectives — and the collectives' share of device busy
    # time, which growing means the step got more communication-bound.
    # Absolute slacks because both are fractions that wobble a few
    # points run to run on quiet captures.
    "overlap_frac": ("higher", 0.05),
    "collective_frac": ("lower", 0.03),
    # the memory layer's gating scalar (schema v11 'memory' records +
    # mem.* gauges, obs/memory.py): the run's worst observed per-chip
    # peak HBM — HIGHER is a regression (a config that crept toward the
    # chip ceiling fails CI before it OOMs a pod). Absolute slack of
    # 1 MiB: allocator peaks wobble by small workspace allocations on
    # otherwise identical runs, and a pure ratio would flag them.
    "peak_hbm_bytes": ("lower", 1024 * 1024),
    # the async-checkpoint layer's gating scalar (goodput ledger bucket,
    # obs/goodput.py; ckpt/checkpoint.py two-phase sharded saves): total
    # wall-clock seconds the step loop spent blocked on checkpoint
    # save/restore. HIGHER is a regression — a save that used to hide
    # behind compute (snapshot-then-write, --async_ckpt) has started
    # blocking again. Absolute slack of 0.25 s: restore ladders and
    # first-save directory creation wobble tenths of a second run to run.
    "ckpt_s": ("lower", 0.25),
    # the co-scheduling layer's gating scalar (goodput ledger bucket,
    # schema v15; obs/goodput.py): total wall-clock seconds this run
    # spent relaunching because the fleet arbiter preempted it for a
    # breached serving SLO (world-change gaps whose resume carried a
    # propagated decision_id with cause serve_breach). HIGHER is a
    # regression — the policy started paying more training time for the
    # same SLO. Absolute slack of 0.25 s, the relaunch-wobble floor.
    "preempt_for_serve_s": ("lower", 0.25),
    # bench-mode per-record fields
    "value": ("higher", 0.0),          # images/sec (or tokens/sec)
    "sec_per_epoch": ("lower", 0.0),
    "step_ms": ("lower", 0.0),
    "step_ms_p50": ("lower", 0.0),
    "step_ms_p95": ("lower", 0.0),
    "step_ms_p99": ("lower", 0.0),
    "mfu": ("higher", 0.005),
    # bench --ckpt records (bench.py checkpoint drill): milliseconds the
    # step loop was blocked per save — the snapshot window for async
    # saves, the whole serialize+CRC+write for sync ones. LOWER is
    # better; absolute slack of 5 ms because host-side device_get of a
    # small model wobbles a few ms on shared CI machines.
    "ckpt_blocked_ms": ("lower", 5.0),
    # serving (``--slo`` gate + bench --serve records, serve/slo.py):
    # latency/queue metrics are lower-is-better; a LOWER-latency
    # candidate is an improvement and must never be flagged.
    # compiled-communication accounting (bench records, shardlint —
    # tpu_dist/analysis/shardlint.py): wire bytes of ONE step derived from
    # the optimized HLO the compiler actually emitted. HIGHER is a
    # regression — GSPMD grew an implicit reshard or a wire leg widened —
    # and the number is static+deterministic (zero slack), so a compiled-
    # comm regression gates in CI, where there is no chip.
    "hlo_wire_bytes_per_step": ("lower", 0.0),
    "requests_per_s": ("higher", 0.0),
    "serve_requests_per_s": ("higher", 0.0),
    "latency_p50_ms": ("lower", 0.0),
    "latency_p99_ms": ("lower", 0.0),
    "serve_latency_p50_ms": ("lower", 0.0),
    "serve_latency_p99_ms": ("lower", 0.0),
    "serve_ttfb_p99_ms": ("lower", 0.0),
    "serve_availability": ("higher", 0.001),
    "batch_occupancy": ("higher", 0.02),
    "serve_batch_occupancy": ("higher", 0.02),
    "serve_queue_depth_max": ("lower", 1.0),
    # longitudinal-archive series (obs/archive.py). multichip_ok is the
    # driver's MULTICHIP_* pass/fail as a 0/1 point — a dry run that
    # stopped passing is a regression. The pod_* gauges are the hub
    # rollups `obs hub --archive` snapshots per interval: dead runs /
    # SLO breaches growing or chips shrinking regress; goodput means
    # carry the history gate's absolute point slack; the stall slack
    # matches data_stall_frac. Integer counters get a 0.5 slack so an
    # exactly-equal count never flags on the band's relative floor.
    "multichip_ok": ("higher", 0.0),
    "pod_runs_dead": ("lower", 0.5),
    "pod_breach_count": ("lower", 0.5),
    "pod_total_chips": ("higher", 0.0),
    "pod_worst_stall_frac": ("lower", 0.02),
    "pod_goodput_frac_train": ("higher", 0.01),
    "pod_goodput_frac_serve": ("higher", 0.01),
}


def direction_of(metric: str) -> Tuple[str, float]:
    """Registry lookup with two documented suffix defaults: ``*_ms`` /
    ``*_s`` / ``*_seconds`` metrics are latencies (lower is better,
    zero slack), ``*_per_s`` are rates (higher). Anything else must be
    registered explicitly — an unknown direction silently guessed wrong
    would invert a gate, so this raises instead."""
    hit = METRIC_DIRECTIONS.get(metric)
    if hit is not None:
        return hit
    if metric.endswith("_per_s"):
        return ("higher", 0.0)
    if metric.endswith(("_ms", "_s", "_seconds")):
        return ("lower", 0.0)
    raise KeyError(
        f"metric {metric!r} has no registered direction "
        "(obs/compare.py METRIC_DIRECTIONS) and no suffix default"
    )


def _table(names: Tuple[str, ...]) -> Tuple[Tuple[str, str, float], ...]:
    return tuple((n, *direction_of(n)) for n in names)


#: history-mode metrics: (key, direction, absolute slack), derived from
#: the registry.
REPORT_METRICS: Tuple[Tuple[str, str, float], ...] = _table((
    "images_per_sec_mean", "step_time_p50_s", "step_time_p95_s",
    "step_time_p99_s", "data_stall_frac", "mfu_mean", "final_loss",
    "final_val_top1", "goodput_frac", "overlap_frac", "collective_frac",
    "peak_hbm_bytes", "ckpt_s", "preempt_for_serve_s",
))

#: the ``--goodput`` gate's metric set: time-to-useful-work only. The
#: fraction is the headline; the stall fraction rides along because a
#: goodput regression's most common cause is an input-pipeline change,
#: and the serve-preemption seconds because a co-scheduling policy that
#: started charging training more for the same SLO is a goodput story
#: even when the fraction hides it in a long run.
GOODPUT_METRICS: Tuple[str, ...] = (
    "goodput_frac", "data_stall_frac", "preempt_for_serve_s",
)

#: the ``--slo`` gate's metric set (serving runs, ``serve`` records):
#: request rate, latency ceilings (upper-bound quantiles in ms),
#: availability, and batching efficiency — directions from the registry,
#: so lower latency is NEVER flagged.
SLO_METRICS: Tuple[Tuple[str, str, float], ...] = _table((
    "serve_requests_per_s", "serve_latency_p50_ms",
    "serve_latency_p99_ms", "serve_ttfb_p99_ms", "serve_availability",
    "serve_batch_occupancy",
))

#: bench-mode per-record fields: (field, direction, absolute slack).
#: ``goodput_frac`` keeps bench's historical wider slack (bench windows
#: are short, the fraction noisier than a whole run's ledger).
BENCH_FIELDS: Tuple[Tuple[str, str, float], ...] = _table((
    "value", "sec_per_epoch", "step_ms", "step_ms_p50", "step_ms_p95",
    "step_ms_p99", "mfu",
    # bench records carry XLA's static per-step memory accounting
    # (``peak_hbm_bytes`` from ``memory_analysis()``) — a static count, so
    # memory regressions gate in CI, where there is no chip
    "peak_hbm_bytes",
    # ...and the compiled-collective wire bytes (shardlint over the
    # optimized HLO), the communication twin of that memory gate
    "hlo_wire_bytes_per_step",
    # ...and the checkpoint drill's blocking window (bench.py --ckpt) —
    # a save that stopped hiding behind the step loop gates here
    "ckpt_blocked_ms",
    # serving bench records (bench.py --serve)
    "requests_per_s", "latency_p50_ms", "latency_p99_ms",
    "batch_occupancy",
)) + (("goodput_frac", "higher", 0.02),)


def _mean(vals: List) -> Optional[float]:
    nums = [v for v in vals if isinstance(v, (int, float))]
    return sum(nums) / len(nums) if nums else None


def report_scalars(report: dict) -> dict:
    """Flatten a :func:`summarize` report into the comparable scalars."""
    epochs = report.get("epochs", [])
    losses = [r.get("loss") for r in epochs if isinstance(r.get("loss"), (int, float))]
    top1s = [
        r.get("val_top1") for r in epochs
        if isinstance(r.get("val_top1"), (int, float))
    ]
    gp = report.get("goodput") or {}
    pas = [
        p for p in (report.get("profile_analyses") or [])
        if not p.get("error")
    ]
    sw = report.get("serve_windows") or []
    return {
        "images_per_sec_mean": report["totals"].get("images_per_sec_mean"),
        "step_time_p50_s": _mean([r.get("step_time_p50_s") for r in epochs]),
        "step_time_p95_s": _mean([r.get("step_time_p95_s") for r in epochs]),
        "step_time_p99_s": _mean([r.get("step_time_p99_s") for r in epochs]),
        "data_stall_frac": _mean([r.get("data_stall_frac") for r in epochs]),
        "mfu_mean": report["totals"].get("mfu_mean"),
        "final_loss": losses[-1] if losses else None,
        "final_val_top1": top1s[-1] if top1s else None,
        # the run-level ledger's fraction (obs/goodput.py): resumed
        # segments folded, restart gaps counted against it
        "goodput_frac": gp.get("goodput_frac"),
        # capture-derived means (profile_analysis records); None — and
        # therefore a skipped row, never a fake pass — on capture-less runs
        "overlap_frac": _mean([p.get("overlap_frac") for p in pas]),
        "collective_frac": _mean([p.get("collective_frac") for p in pas]),
        # serving SLO means over the run's serve windows (schema v10);
        # None — skipped, never faked — on a training-only log. The
        # ``--slo`` gate compares exactly these (SLO_METRICS).
        "serve_requests_per_s": _mean([w.get("requests_per_s") for w in sw]),
        "serve_latency_p50_ms": _mean([w.get("latency_p50_ms") for w in sw]),
        "serve_latency_p99_ms": _mean([w.get("latency_p99_ms") for w in sw]),
        "serve_ttfb_p99_ms": _mean([w.get("ttfb_p99_ms") for w in sw]),
        "serve_availability": _mean([w.get("availability") for w in sw]),
        "serve_batch_occupancy": _mean([w.get("batch_occupancy") for w in sw]),
        # the memory layer's worst observed per-chip peak (schema v11);
        # None — skipped, never faked — on a memory-less / pre-v11 log
        "peak_hbm_bytes": (report.get("memory") or {}).get("peak_hbm_bytes"),
        # the async-checkpoint layer's blocking total (goodput ledger
        # 'ckpt' bucket); None — skipped, never faked — on a ledger-less
        # log. Gates the two-phase save's whole point: hiding the write.
        "ckpt_s": gp.get("ckpt_s"),
        # the co-scheduling layer's chosen cost (goodput ledger
        # 'preempt_for_serve' bucket, schema v15); None — skipped,
        # never faked — on a ledger-less log
        "preempt_for_serve_s": gp.get("preempt_for_serve_s"),
    }


def _row(
    metric: str, direction: str, slack: float,
    base, cand, threshold: float,
) -> dict:
    if not isinstance(base, (int, float)) or not isinstance(cand, (int, float)):
        return {"metric": metric, "baseline": base, "candidate": cand,
                "verdict": "skipped"}
    worse_by = (base - cand) if direction == "higher" else (cand - base)
    allowed = abs(base) * threshold + slack
    regressed = worse_by > allowed
    out = {
        "metric": metric,
        "baseline": base,
        "candidate": cand,
        "delta": round(cand - base, 6),
        "verdict": "REGRESSED" if regressed else "ok",
    }
    if base:
        out["delta_frac"] = round((cand - base) / abs(base), 4)
    return out


def compare_scalars(
    base: dict, cand: dict, threshold: float = 0.05,
    goodput_only: bool = False, slo_only: bool = False,
) -> dict:
    if slo_only:
        metrics = list(SLO_METRICS)
    else:
        metrics = [
            m for m in REPORT_METRICS
            if not goodput_only or m[0] in GOODPUT_METRICS
        ]
    rows = [
        _row(key, direction, slack, base.get(key), cand.get(key), threshold)
        for key, direction, slack in metrics
    ]
    return _result(rows, threshold)


def _result(rows: List[dict], threshold: float) -> dict:
    return {
        "threshold": threshold,
        "rows": rows,
        "regressions": sum(r["verdict"] == "REGRESSED" for r in rows),
        "compared": sum(r["verdict"] not in ("skipped", "STALE") for r in rows),
        "skipped": sum(r["verdict"] == "skipped" for r in rows),
        "stale": sum(r["verdict"] == "STALE" for r in rows),
    }


def capture_fingerprint(rec: dict) -> Optional[tuple]:
    """The bench record's capture identity (``bench.py`` stamps hostname,
    a per-invocation id, and a monotonic capture time into every record).
    Two records with the SAME fingerprint are one physical capture — a
    candidate re-emitting the baseline's fingerprint is a stale copy,
    not a fresh measurement. None on pre-stamp (legacy) records."""
    cap = rec.get("capture")
    if isinstance(cap, dict) and cap.get("bench_run_id"):
        return (cap.get("host"), cap.get("bench_run_id"), cap.get("mono_s"))
    return None


# -- input loading -----------------------------------------------------------


def load_history_scalars(path: str) -> dict:
    """``--log_file`` JSONL → comparable scalars; raises ValueError on an
    empty/unusable file (a gate comparing nothing must fail loudly). A
    serving-only log (``serve`` windows, no ``train_epoch`` records) is
    usable — the ``--slo`` gate compares exactly those."""
    records, _bad = summ.load_records(path)
    if not records:
        raise ValueError(f"no records in {path}")
    report = summ.summarize(records)
    if not report["epochs"] and not report.get("serve_windows"):
        raise ValueError(f"no train_epoch or serve records in {path}")
    scalars = report_scalars(report)
    scalars["_run_id"] = report.get("run_id")
    return scalars


def load_bench_records(path: str) -> dict:
    """bench.py output (JSON object per line) → ``{metric_name: record}``.
    Tolerates a torn tail like the history loader; raises ValueError when
    nothing parses."""
    return {rec["metric"]: rec for rec in _load_bench_list(path)}


def compare_bench(base: dict, cand: dict, threshold: float = 0.05) -> dict:
    """Compare two ``{metric: record}`` bench maps field-by-field; metrics
    present on only one side are reported as skipped rows."""
    rows: List[dict] = []
    for name in sorted(set(base) | set(cand)):
        b, c = base.get(name), cand.get(name)
        if b is None or c is None:
            rows.append({
                "metric": name,
                "baseline": None if b is None else "present",
                "candidate": None if c is None else "present",
                "verdict": "skipped",
            })
            continue
        fp_b, fp_c = capture_fingerprint(b), capture_fingerprint(c)
        if (fp_b is not None and fp_b == fp_c) or b.get("stale") or c.get("stale"):
            # the candidate is a byte-identical re-emission of the
            # baseline's capture (the r03–r05 staleness failure mode), or
            # either side carries bench's own stale:true last-good-
            # fallback stamp: comparing those numbers would read as "no
            # regression" when nothing was measured — flag, don't compare
            rows.append({
                "metric": name,
                "baseline": (
                    "stale capture" if b.get("stale")
                    else "capture " + str((fp_b or ("?",) * 2)[1])
                ),
                "candidate": (
                    "stale capture" if c.get("stale") else "same capture"
                ),
                "verdict": "STALE",
            })
            continue
        for field, direction, slack in BENCH_FIELDS:
            if field not in b and field not in c:
                continue
            rows.append(_row(
                f"{name}.{field}", direction, slack,
                b.get(field), c.get(field), threshold,
            ))
    return _result(rows, threshold)


def compare_files(
    baseline: str, candidate: str, *,
    threshold: float = 0.05, bench: bool = False,
    goodput_only: bool = False, slo_only: bool = False,
) -> dict:
    """The CLI engine: load both inputs and diff. Raises OSError on an
    unreadable file and ValueError on an unusable one — the caller maps
    both to exit 2 (a broken gate, distinct from exit 1's regression).
    ``goodput_only`` (the ``--goodput`` flag) restricts the gate to the
    time-to-useful-work metrics; ``slo_only`` (``--slo``) to the serving
    SLO metrics (``serve`` records, directions from the registry — a
    lower-latency candidate is never flagged). Inputs without the
    gated records then compare nothing, which the CLI surfaces as a
    broken gate (exit 2) rather than a silent pass."""
    if bench and (goodput_only or slo_only):
        raise ValueError(
            "--goodput/--slo gate history-mode logs; bench records carry "
            "their serving/goodput fields as ordinary compared fields"
        )
    if goodput_only and slo_only:
        raise ValueError("--goodput and --slo are separate gates; pick one")
    if bench:
        result = compare_bench(
            load_bench_records(baseline), load_bench_records(candidate),
            threshold,
        )
    else:
        b = load_history_scalars(baseline)
        c = load_history_scalars(candidate)
        result = compare_scalars(
            b, c, threshold, goodput_only=goodput_only, slo_only=slo_only,
        )
        result["baseline_run_id"] = b.get("_run_id")
        result["candidate_run_id"] = c.get("_run_id")
    result["baseline"] = baseline
    result["candidate"] = candidate
    return result


def format_text(result: dict) -> str:
    lines = [
        f"compare: baseline {result['baseline']} vs candidate "
        f"{result['candidate']} (threshold {result['threshold'] * 100:g}%)"
    ]
    w = max([len(r["metric"]) for r in result["rows"]] + [6])

    def cell(v):
        if isinstance(v, float):
            return format(v, ".6g").rjust(12)
        return str(v if v is not None else "-").rjust(12)

    lines.append(f"  {'metric'.ljust(w)} {'baseline':>12} {'candidate':>12} "
                 f"{'delta%':>8}  verdict")
    for r in result["rows"]:
        frac = r.get("delta_frac")
        lines.append(
            f"  {r['metric'].ljust(w)} {cell(r.get('baseline'))} "
            f"{cell(r.get('candidate'))} "
            f"{(format(frac * 100, '+.1f') if frac is not None else '-'):>8}"
            f"  {r['verdict']}"
        )
    lines.append(
        f"compare: {result['regressions']} regression(s) over "
        f"{result['compared']} compared metric(s)"
        + (f", {result['skipped']} skipped" if result["skipped"] else "")
        + (
            f", {result['stale']} STALE (candidate re-emits the "
            "baseline's capture — not a fresh measurement)"
            if result.get("stale") else ""
        )
    )
    return "\n".join(lines)


# -- bench staleness report (`obs summarize --bench`) ------------------------


def _load_bench_list(path: str) -> List[dict]:
    """Order-preserving bench loader that keeps duplicates — the
    staleness report must SEE re-emitted records, which the by-metric
    dict of :func:`load_bench_records` (built on this) collapses."""
    out: List[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict) and rec.get("metric"):
                out.append(rec)
    if not out:
        raise ValueError(f"no bench records in {path}")
    return out


def bench_report(path: str) -> dict:
    """Per-record bench summary with capture-staleness flags: a record is
    ``stale`` when it carries the self-declared ``stale: true`` stamp
    (bench's last-good fallback) or repeats an earlier record's capture
    fingerprint byte-for-byte (a re-emission inside one artifact)."""
    seen: dict = {}
    rows: List[dict] = []
    for rec in _load_bench_list(path):
        fp = capture_fingerprint(rec)
        reemitted = fp is not None and fp in seen
        row = {
            "metric": rec.get("metric"),
            "value": rec.get("value"),
            "unit": rec.get("unit"),
            "mfu": rec.get("mfu"),
            "stale": bool(rec.get("stale")) or reemitted,
        }
        if fp is not None:
            row["capture"] = {
                "host": fp[0], "bench_run_id": fp[1], "mono_s": fp[2],
            }
            if reemitted:
                row["stale_of"] = seen[fp]
            else:
                seen[fp] = rec.get("metric")
        if rec.get("age_days") is not None:
            row["age_days"] = rec["age_days"]
        rows.append(row)
    return {
        "path": path,
        "records": rows,
        "n_stale": sum(r["stale"] for r in rows),
        "n_unfingerprinted": sum("capture" not in r for r in rows),
    }


def format_bench_report(report: dict) -> str:
    lines = [
        f"bench {report['path']}: {len(report['records'])} record(s)"
        + (f", {report['n_stale']} STALE" if report["n_stale"] else "")
        + (
            f", {report['n_unfingerprinted']} without capture fingerprint "
            "(pre-stamp)"
            if report["n_unfingerprinted"] else ""
        )
    ]
    w = max([len(str(r["metric"])) for r in report["records"]] + [6])
    for r in report["records"]:
        cap = r.get("capture") or {}
        lines.append(
            f"  {str(r['metric']).ljust(w)} "
            f"{str(r.get('value')).rjust(10)} {str(r.get('unit') or ''):<11}"
            + (
                f" capture {cap.get('bench_run_id')}@{cap.get('host')}"
                if cap else " (no fingerprint)"
            )
            + (
                "  STALE"
                + (f" (re-emits {r['stale_of']})" if r.get("stale_of") else "")
                + (
                    f" ({r['age_days']}d old)"
                    if r.get("age_days") is not None else ""
                )
                if r["stale"] else ""
            )
        )
    return "\n".join(lines)
