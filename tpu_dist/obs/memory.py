"""HBM observability — the structured memory ledger, pre-flight
feasibility lint, and OOM forensics (``docs/observability.md`` "HBM
ledger & OOM forensics").

Time is fully instrumented (goodput ledger, xprof attribution, serving
histograms); this module instruments the OTHER binding constraint. Pod-
scale runs die on memory long before they die on FLOPs (PAPERS.md
"Exploring the limits of Concurrency in ML Training on Google TPUs"),
and ZeRO-1 exists entirely because of per-chip memory (arXiv:2004.13336)
— yet before this module an OOM here was an unparsed
``RESOURCE_EXHAUSTED`` traceback and the only memory telemetry was two
epoch-end allocator gauges. Four layers, all host-side metadata work
(rule TD115 pins that arming every one of them leaves the traced train
step byte-identical):

* **Static per-leaf ledger** — :func:`static_ledger` walks pytrees
  (params / opt state / error-feedback residuals / BN state / a batch)
  and accounts bytes from avals + shardings alone: shape x itemsize per
  leaf, at the leaf's SHARDED extent per device (a ZeRO-1 flat momentum
  vector laid ``P('data')`` over 8 devices counts ceil(L/8) elements per
  chip, not L). CPU-valid: no device transfer, no compile.
* **Live census + reconciliation** — :func:`live_census` sums
  ``jax.live_arrays()`` per device (again from sharding metadata);
  :func:`reconcile` sets it against the allocator's own
  ``memory_stats()`` counters so that ``attributed + unattributed ==
  bytes_in_use`` holds EXACTLY, by construction: unattributed is
  *defined* as the difference — XLA workspace, fragmentation, and
  donated-but-alive handles get their own tracked gauge instead of
  silently inflating "model memory". Where the backend keeps no
  allocator stats (CPU), the census itself is the authority
  (``source: "census"``) and the invariant still holds exactly.
* **Pre-flight feasibility** — :func:`feasibility` /
  :func:`preflight_check` compare the static estimate against the
  per-chip HBM budget (``costmodel.CHIP_HBM_BYTES``) scaled by a
  headroom fraction, BEFORE the first compile can OOM; the trainer wires
  it as ``--memory_check warn|refuse`` with ``--memory_headroom`` — the
  lint-style HBM-infeasibility rule ROADMAP item 3 names.
* **OOM forensics** — :func:`parse_resource_exhausted` turns XLA's
  ``RESOURCE_EXHAUSTED`` text (both the GPU/BFC "while trying to
  allocate N bytes" shape with its "Largest program allocations" buffer
  table and the TPU "Used X of Y hbm / Exceeded hbm capacity by Z"
  shape) into a typed report; the trainer stamps it into the flight
  ring, writes the full report + the ledger snapshot that was live as
  ``oom.json`` in ``--crash_dir``, and ``obs postmortem`` classifies the
  rank's verdict as ``oom``.

Everything lands in the ordinary telemetry plumbing: ``mem.*`` gauges in
the counter registry (-> every history record and OpenMetrics
exposition), ONE ``memory`` history record per run (schema v11,
additive) at first dispatch, a ``memory_headroom_low`` built-in alert
rule, summarize/tail/pod rendering, and a ``peak_hbm_bytes`` scalar the
``obs compare`` gate regresses on (higher = worse, the direction
registry's first bytes metric).

This module imports jax ONLY inside the functions that need a backend
(the ledger/census); the parser, reconciliation, feasibility math, and
every formatter are plain stdlib — they run in the postmortem CLI on any
laptop the crash files were copied to.

CLI: ``python -m tpu_dist.obs memory <run.jsonl>`` (ledger report over a
history) and ``python -m tpu_dist.obs memory --oom <traceback.txt>``
(parse a raw RESOURCE_EXHAUSTED text). Exit codes: 0 report, 1 no
memory telemetry / unparseable, 2 unreadable input.
"""

from __future__ import annotations

import json
import math
import re
import time
from typing import Dict, List, Optional

from tpu_dist.obs import counters as counters_lib

#: Per-section leaves listed by size in the ledger (the rest are summed).
TOP_LEAVES = 5

#: Canonical per-rank OOM-report artifact name inside a ``--crash_dir``
#: (rank 0 bare, rank k ``.h<k>`` — the flight-ring naming scheme).
OOM_NAME = "oom.json"


class InfeasibleMemoryError(ValueError):
    """The static ledger does not fit the per-chip HBM budget and
    ``--memory_check refuse`` asked for a hard stop before compiling."""


# --------------------------------------------------------------------------
# Static per-leaf ledger — avals + shardings, no device work.
# --------------------------------------------------------------------------


def _leaf_entry(path: str, leaf) -> Optional[dict]:
    """One leaf's byte accounting from metadata alone: ``bytes_total`` =
    shape x itemsize; ``bytes_per_device`` = the SHARDED extent (what one
    chip actually holds — ``sharding.shard_shape``), equal to the total
    on replicated/host leaves. None for non-array leaves."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return None
    try:
        import numpy as np  # noqa: PLC0415

        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        return None
    total = int(math.prod(shape)) * itemsize if shape else itemsize
    per_device = total
    sharded = False
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        try:
            shard_shape = sharding.shard_shape(tuple(shape))
            per_device = int(math.prod(shard_shape)) * itemsize if shard_shape else itemsize
            sharded = per_device < total
        except Exception:  # tpu-dist: ignore[TD006] — an exotic sharding
            pass  # degrades to the replicated (total) count, never raises
    return {
        "path": path,
        "bytes_per_device": per_device,
        "bytes_total": total,
        "shape": [int(s) for s in shape],
        "dtype": str(dtype),
        "sharded": sharded,
    }


def static_ledger(**sections) -> dict:
    """Per-leaf static accounting of named pytrees (``params=...,
    opt_state=..., ef=..., bn_state=..., batch=...``): per section the
    per-device and total bytes, leaf count, sharded-leaf count, and the
    :data:`TOP_LEAVES` largest leaves by per-device bytes. Sections that
    are None/empty are recorded with zero bytes (the report says "no EF
    state" instead of omitting the row)."""
    import jax  # noqa: PLC0415

    out_sections: Dict[str, dict] = {}
    per_device = total = leaves = 0
    for name, tree in sections.items():
        entries: List[dict] = []
        if tree is not None:
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
                e = _leaf_entry(jax.tree_util.keystr(path), leaf)
                if e is not None:
                    entries.append(e)
        sec_dev = sum(e["bytes_per_device"] for e in entries)
        sec_tot = sum(e["bytes_total"] for e in entries)
        entries.sort(key=lambda e: -e["bytes_per_device"])
        out_sections[name] = {
            "bytes_per_device": sec_dev,
            "bytes_total": sec_tot,
            "n_leaves": len(entries),
            "sharded_leaves": sum(e["sharded"] for e in entries),
            "top": entries[:TOP_LEAVES],
        }
        per_device += sec_dev
        total += sec_tot
        leaves += len(entries)
    return {
        "sections": out_sections,
        "bytes_per_device": per_device,
        "bytes_total": total,
        "n_leaves": leaves,
    }


# --------------------------------------------------------------------------
# Live census + allocator reconciliation.
# --------------------------------------------------------------------------


def live_census() -> dict:
    """Sum ``jax.live_arrays()`` per device from sharding metadata (no
    transfer, no sync): ``{"n_arrays", "bytes_total", "bytes_by_device":
    {device_id: bytes}, "bytes_device0"}``. ``bytes_device0`` is the
    first local device's attribution — what :func:`reconcile` sets
    against that device's allocator counters."""
    import jax  # noqa: PLC0415

    by_device: Dict[int, int] = {}
    n = 0
    total = 0
    for arr in jax.live_arrays():
        e = _leaf_entry("", arr)
        if e is None:
            continue
        n += 1
        total += e["bytes_total"]
        sharding = getattr(arr, "sharding", None)
        devices = sorted(
            getattr(sharding, "device_set", None) or [],
            key=lambda d: d.id,
        )
        if not devices:
            devices = [jax.local_devices()[0]]
        for d in devices:
            by_device[d.id] = by_device.get(d.id, 0) + e["bytes_per_device"]
    dev0 = jax.local_devices()[0].id
    return {
        "n_arrays": n,
        "bytes_total": total,
        "bytes_by_device": {str(k): v for k, v in sorted(by_device.items())},
        "bytes_device0": by_device.get(dev0, 0),
    }


def reconcile(census: dict, allocator: Optional[dict]) -> dict:
    """The ledger's closing identity: ``attributed + unattributed ==
    bytes_in_use``, EXACT by construction.

    ``attributed`` is the census's first-device bytes (every live array
    the process can name); ``allocator`` must therefore be the SAME
    device's counters (:func:`ledger` passes device 0's raw
    ``memory_stats()`` — NOT :func:`costmodel.device_memory_stats`,
    whose scalars report the worst chip: pairing device 0's census with
    another chip's allocator would book cross-device sharding skew as
    workspace). ``unattributed`` is *defined* as that device's
    ``bytes_in_use`` minus the attribution — XLA workspace, allocator
    fragmentation, and (negative) donated buffers whose Python handles
    outlive their device memory. Where the backend keeps no allocator
    stats (``allocator`` None/empty — CPU), the census itself is the
    authority: ``bytes_in_use := attributed``, ``unattributed := 0``,
    ``source: "census"`` — the invariant holds exactly either way, so a
    consumer never needs a backend-conditional code path."""
    attributed = int(census.get("bytes_device0", 0))
    in_use = (allocator or {}).get("bytes_in_use")
    if isinstance(in_use, (int, float)):
        in_use = int(in_use)
        return {
            "attributed_bytes": attributed,
            "unattributed_bytes": in_use - attributed,
            "bytes_in_use": in_use,
            "source": "allocator",
        }
    return {
        "attributed_bytes": attributed,
        "unattributed_bytes": 0,
        "bytes_in_use": attributed,
        "source": "census",
    }


def ledger(static: Optional[dict] = None, xla: Optional[dict] = None) -> dict:
    """One full ledger snapshot: the construction-time static accounting
    (``static``), the compile-time ``memory_analysis()`` waterfall
    (``xla`` — ``costmodel.memory_analysis_bytes``), the live census,
    the allocator counters (per-device max/min/skew —
    ``costmodel.device_memory_stats``), and the reconciliation. This is
    the ``memory`` history record (schema v11) and the crash snapshot
    ``oom.json`` embeds."""
    import jax  # noqa: PLC0415

    from tpu_dist.obs import costmodel  # noqa: PLC0415

    census = live_census()
    allocator = costmodel.device_memory_stats()
    # reconcile against DEVICE 0's raw counters — the same device the
    # census's bytes_device0 attributes. The worst-chip scalars in
    # `allocator` belong to the skew report, not the identity: pairing
    # device 0's census with another chip's allocator would book
    # cross-device sharding skew as workspace (see reconcile()).
    try:
        dev0_stats = jax.local_devices()[0].memory_stats()
    except Exception:  # tpu-dist: ignore[TD006] — stat-less backend:
        dev0_stats = None  # reconcile degrades to census authority
    rec: dict = {
        "census": census,
        "reconciliation": reconcile(census, dev0_stats),
    }
    if static is not None:
        rec["static"] = static
    if xla is not None:
        rec["xla"] = xla
    if allocator is not None:
        rec["allocator"] = allocator
    return rec


def publish_ledger(rec: dict) -> None:
    """Stamp a ledger snapshot into the ``mem.*`` gauges — every later
    history record and OpenMetrics exposition carries the numbers
    (``counters.snapshot`` feeds both)."""
    static = rec.get("static") or {}
    if static.get("bytes_per_device"):
        counters_lib.set_gauge(
            "mem.static_bytes_per_device", static["bytes_per_device"]
        )
    xla = rec.get("xla") or {}
    for key, gauge in (
        ("argument_bytes", "mem.xla_argument_bytes"),
        ("output_bytes", "mem.xla_output_bytes"),
        ("temp_bytes", "mem.xla_temp_bytes"),
        ("generated_code_bytes", "mem.xla_code_bytes"),
        ("peak_bytes", "mem.xla_peak_bytes"),
    ):
        v = xla.get(key)
        if isinstance(v, (int, float)):
            counters_lib.set_gauge(gauge, int(v))
    rc = rec.get("reconciliation") or {}
    for key, gauge in (
        ("attributed_bytes", "mem.attributed_bytes"),
        ("unattributed_bytes", "mem.unattributed_bytes"),
    ):
        v = rc.get(key)
        if isinstance(v, (int, float)):
            counters_lib.set_gauge(gauge, int(v))


def record_peak_hbm(rec: dict) -> Optional[int]:
    """The snapshot's single gating scalar: the worst chip's allocator
    peak when the backend reports one (the TRUE number), else XLA's
    static ``peak_bytes`` estimate, else the reconciled ``bytes_in_use``
    (census authority on CPU). None on an empty record."""
    alloc = rec.get("allocator") or {}
    v = alloc.get("peak_bytes", alloc.get("peak_bytes_in_use"))
    if isinstance(v, (int, float)) and v > 0:
        return int(v)
    xla = rec.get("xla") or {}
    v = xla.get("peak_bytes")
    if isinstance(v, (int, float)) and v > 0:
        return int(v)
    v = (rec.get("reconciliation") or {}).get("bytes_in_use")
    return int(v) if isinstance(v, (int, float)) and v > 0 else None


# --------------------------------------------------------------------------
# Pre-flight feasibility — the HBM lint (ROADMAP item 3).
# --------------------------------------------------------------------------


def feasibility(
    required_bytes: int, budget_bytes: int, headroom: float = 0.9,
) -> dict:
    """Does a per-device static requirement fit a per-chip HBM budget?
    ``headroom`` is the fraction of the budget the STATIC estimate may
    claim — the rest is reserved for XLA temps/workspace/fragmentation,
    which the static ledger cannot see (the ``unattributed`` gauge
    measures them after the fact). ``utilization`` is required/budget
    (headroom-independent, the number humans compare across chips)."""
    if budget_bytes <= 0:
        raise ValueError(f"budget_bytes must be positive, got {budget_bytes}")
    if not 0.0 < headroom <= 1.0:
        raise ValueError(f"headroom must be in (0, 1], got {headroom}")
    allowed = int(budget_bytes * headroom)
    return {
        "required_bytes": int(required_bytes),
        "budget_bytes": int(budget_bytes),
        "headroom": headroom,
        "allowed_bytes": allowed,
        "utilization": round(required_bytes / budget_bytes, 4),
        "fits": required_bytes <= allowed,
    }


def preflight_check(
    required_bytes: int,
    *,
    budget_bytes: Optional[int] = None,
    headroom: float = 0.9,
    action: str = "warn",
    chip_kind: Optional[str] = None,
) -> Optional[dict]:
    """The trainer's pre-compile HBM lint. ``budget_bytes`` overrides the
    chip-table lookup (``costmodel.chip_hbm_bytes`` — tests, exotic
    parts); an unknown chip with no override (CPU emulation) returns
    None: no budget, no lint, never a guess. ``action``: ``"off"`` skips
    entirely, ``"warn"`` returns the report (the caller prints),
    ``"refuse"`` raises :class:`InfeasibleMemoryError` on a miss — the
    run stops BEFORE the first compile can OOM."""
    if action not in ("off", "warn", "refuse"):
        raise ValueError(
            f"memory_check must be off|warn|refuse, got {action!r}"
        )
    if action == "off":
        return None
    if budget_bytes is None:
        from tpu_dist.obs import costmodel  # noqa: PLC0415

        budget_bytes = costmodel.chip_hbm_bytes(chip_kind)
    if budget_bytes is None:
        return None
    report = feasibility(required_bytes, budget_bytes, headroom)
    if not report["fits"] and action == "refuse":
        raise InfeasibleMemoryError(
            f"static HBM requirement {fmt_bytes(report['required_bytes'])} "
            f"per device exceeds {headroom:.0%} of the "
            f"{fmt_bytes(report['budget_bytes'])} per-chip budget "
            f"(allowed {fmt_bytes(report['allowed_bytes'])}) — the config "
            "cannot fit before XLA temps are even counted; shard more "
            "(--shard_weight_update/--fsdp), shrink the batch, or raise "
            "--memory_headroom / pass --memory_check warn to proceed anyway"
        )
    return report


# --------------------------------------------------------------------------
# OOM forensics — RESOURCE_EXHAUSTED text -> typed report.
# --------------------------------------------------------------------------

#: "2.50G" / "750.6M" / "1.1KiB" / "123B" — XLA's human-size rendering.
_SIZE_RE = r"(\d+(?:\.\d+)?)\s*([KMGTP]i?B?|B|bytes?)"
_OOM_MARKERS = (
    "RESOURCE_EXHAUSTED", "Out of memory", "Ran out of memory",
    "OOM when allocating",
)
#: Multiplier per size-prefix letter; the ``iB``/``B`` tail and letter
#: case are normalized away in :func:`_to_bytes` (the size regexes run
#: IGNORECASE, so a lowercase ``2.5g`` must not silently parse as 2 B).
_UNIT_PREFIX = {
    "K": 1024, "M": 1024 ** 2, "G": 1024 ** 3,
    "T": 1024 ** 4, "P": 1024 ** 5,
}

_ALLOCATE_RE = re.compile(
    r"allocat\w+\s+(?:of\s+)?" + _SIZE_RE, re.IGNORECASE
)
_USED_OF_RE = re.compile(
    r"Used\s+" + _SIZE_RE + r"\s+of\s+" + _SIZE_RE, re.IGNORECASE
)
_EXCEEDED_RE = re.compile(
    r"Exceeded\s+\w+\s+capacity\s+by\s+" + _SIZE_RE, re.IGNORECASE
)
_BUFFER_RE = re.compile(r"^\s*(\d+)\.\s+Size:\s*" + _SIZE_RE)
_SHAPE_RE = re.compile(r"^\s*Shape:\s*(\S.*)$")
_OP_RE = re.compile(r'^\s*Operator:\s*op_name="([^"]*)"')
_XLA_LABEL_RE = re.compile(r"^\s*XLA Label:\s*(\S.*)$")


def _to_bytes(num: str, unit: str) -> int:
    u = unit.strip()
    if u.lower() in ("b", "byte", "bytes"):
        return int(float(num))
    return int(float(num) * _UNIT_PREFIX.get(u[0].upper(), 1))


def parse_resource_exhausted(text: str) -> Optional[dict]:
    """Structure an XLA ``RESOURCE_EXHAUSTED`` message. Returns None when
    the text carries no OOM marker at all (garbage / a different error);
    otherwise a typed report with whatever the (possibly TRUNCATED —
    flight-ring slots cap messages at 200 chars) text still holds:

    * ``headline`` — the first marker line, trimmed,
    * ``requested_bytes`` — the failed allocation ("while trying to
      allocate 2.50G"),
    * ``used_bytes`` / ``limit_bytes`` / ``excess_bytes`` — the TPU
      "Used X of Y hbm … Exceeded hbm capacity by Z" accounting,
    * ``buffers`` — the "Largest program allocations" table, each entry
      ``{rank, size_bytes, shape?, op?}`` (up to 16),
    * ``buffers_bytes`` — their sum.

    Absent fields were simply not in the text; a report with only a
    headline is still a report (the truncated-ring case)."""
    if not text or not any(m in text for m in _OOM_MARKERS):
        return None
    report: dict = {"kind": "oom"}
    for line in text.splitlines():
        if any(m in line for m in _OOM_MARKERS):
            report["headline"] = line.strip()[:240]
            break
    m = _ALLOCATE_RE.search(text)
    if m:
        report["requested_bytes"] = _to_bytes(m.group(1), m.group(2))
    m = _USED_OF_RE.search(text)
    if m:
        report["used_bytes"] = _to_bytes(m.group(1), m.group(2))
        report["limit_bytes"] = _to_bytes(m.group(3), m.group(4))
    m = _EXCEEDED_RE.search(text)
    if m:
        report["excess_bytes"] = _to_bytes(m.group(1), m.group(2))
    buffers: List[dict] = []
    cur: Optional[dict] = None
    for line in text.splitlines():
        bm = _BUFFER_RE.match(line)
        if bm:
            if len(buffers) >= 16:
                break
            cur = {
                "rank": int(bm.group(1)),
                "size_bytes": _to_bytes(bm.group(2), bm.group(3)),
            }
            buffers.append(cur)
            continue
        if cur is None:
            continue
        sm = _SHAPE_RE.match(line)
        if sm:
            cur["shape"] = sm.group(1).strip()[:120]
            continue
        om = _OP_RE.match(line) or _XLA_LABEL_RE.match(line)
        if om and "op" not in cur:
            cur["op"] = om.group(1).strip()[:160]
    if buffers:
        report["buffers"] = buffers
        report["buffers_bytes"] = sum(b["size_bytes"] for b in buffers)
    return report


def oom_summary_line(report: dict) -> str:
    """One human line for the rank-0 warning / tail event / postmortem:
    ``'OOM: requested 2.5GiB, used 15.9GiB of 16.0GiB (3 largest buffers
    account for 12.1GiB)'``."""
    parts = []
    if report.get("requested_bytes"):
        parts.append(f"requested {fmt_bytes(report['requested_bytes'])}")
    if report.get("used_bytes") and report.get("limit_bytes"):
        parts.append(
            f"used {fmt_bytes(report['used_bytes'])} of "
            f"{fmt_bytes(report['limit_bytes'])}"
        )
    elif report.get("excess_bytes"):
        parts.append(f"over capacity by {fmt_bytes(report['excess_bytes'])}")
    if report.get("buffers"):
        parts.append(
            f"{len(report['buffers'])} largest buffers account for "
            f"{fmt_bytes(report.get('buffers_bytes', 0))}"
        )
    return "OOM: " + (", ".join(parts) if parts else
                      report.get("headline", "RESOURCE_EXHAUSTED"))


def write_oom_report(
    path: str, report: dict, snapshot: Optional[dict] = None,
) -> Optional[str]:
    """The crash artifact: the parsed allocation report plus the ledger
    snapshot that was live at the time, as one JSON next to the flight
    ring. Never raises — a full disk must not mask the OOM that is
    already propagating."""
    rec = {"ts": round(time.time(), 3), "oom": report}
    if snapshot:
        rec["ledger"] = snapshot
    try:
        # tpu-dist: ignore[TD002] — per-rank artifact by construction:
        # the caller derives one oom.json path per rank (per_rank_path),
        # exactly the flight-ring discipline
        with open(path, "w") as f:
            json.dump(rec, f, indent=2, default=str)
    except OSError:
        counters_lib.inc("mem.oom_report_errors")
        return None
    return path


def read_oom_report(path: str) -> Optional[dict]:
    """Postmortem-side read of :func:`write_oom_report`'s artifact; None
    on a missing/torn file (the expected input after a crash)."""
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError):
        return None
    return rec if isinstance(rec, dict) else None


# --------------------------------------------------------------------------
# Formatting — shared by the CLI, summarize, tail, and the trainer line.
# --------------------------------------------------------------------------


def fmt_bytes(n) -> str:
    """Human bytes: ``'1.5GiB'`` / ``'320.0MiB'`` / ``'512B'`` / ``'-'``."""
    if not isinstance(n, (int, float)):
        return "-"
    neg = n < 0
    v = float(abs(n))
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if v < 1024 or unit == "TiB":
            body = f"{v:.0f}B" if unit == "B" else f"{v:.1f}{unit}"
            return ("-" if neg else "") + body
        v /= 1024
    return str(n)


def summary_line(rec: dict) -> str:
    """One line per ledger snapshot — trainer rank-0 print, ``obs tail``
    event, and the pod report share it so the renderings cannot drift."""
    static = rec.get("static") or {}
    xla = rec.get("xla") or {}
    rc = rec.get("reconciliation") or {}
    parts = []
    if static.get("bytes_per_device"):
        parts.append(f"static {fmt_bytes(static['bytes_per_device'])}/device")
    if isinstance(xla.get("peak_bytes"), (int, float)):
        parts.append(f"xla peak {fmt_bytes(xla['peak_bytes'])}")
    if rc:
        parts.append(
            f"in use {fmt_bytes(rc.get('bytes_in_use'))} "
            f"(attributed {fmt_bytes(rc.get('attributed_bytes'))} + "
            f"unattributed {fmt_bytes(rc.get('unattributed_bytes'))}, "
            f"{rc.get('source')})"
        )
    return "memory ledger: " + (", ".join(parts) or "(empty)")


def format_ledger_text(rec: dict) -> str:
    """The full ledger rendering (``obs memory``): per-section table,
    the XLA waterfall, the reconciliation identity, allocator skew."""
    lines = [summary_line(rec)]
    static = rec.get("static") or {}
    sections = static.get("sections") or {}
    if sections:
        lines.append(
            f"  {'section':>10} {'per-device':>12} {'total':>12} "
            f"{'leaves':>7} {'sharded':>8}"
        )
        for name in sorted(
            sections, key=lambda n: -sections[n]["bytes_per_device"]
        ):
            s = sections[name]
            lines.append(
                f"  {name:>10} {fmt_bytes(s['bytes_per_device']):>12} "
                f"{fmt_bytes(s['bytes_total']):>12} {s['n_leaves']:>7} "
                f"{s['sharded_leaves']:>8}"
            )
            for e in s.get("top") or []:
                lines.append(
                    f"      {fmt_bytes(e['bytes_per_device']):>10}  "
                    f"{e['path']} {e['dtype']}{e['shape']}"
                    + (" [sharded]" if e.get("sharded") else "")
                )
    xla = rec.get("xla") or {}
    if xla:
        lines.append(
            "  xla waterfall: args "
            f"{fmt_bytes(xla.get('argument_bytes'))}, outputs "
            f"{fmt_bytes(xla.get('output_bytes'))}, temps "
            f"{fmt_bytes(xla.get('temp_bytes'))}, codegen "
            f"{fmt_bytes(xla.get('generated_code_bytes'))} -> peak "
            f"{fmt_bytes(xla.get('peak_bytes'))}"
        )
    alloc = rec.get("allocator") or {}
    if alloc:
        skew = alloc.get("bytes_in_use_skew")
        lines.append(
            "  allocator: in use "
            f"{fmt_bytes(alloc.get('bytes_in_use'))} (worst chip)"
            + (
                f", min {fmt_bytes(alloc.get('bytes_in_use_min'))}, "
                f"skew {fmt_bytes(skew)}"
                if skew is not None else ""
            )
            + (
                f", peak {fmt_bytes(alloc.get('peak_bytes_in_use'))}"
                if alloc.get("peak_bytes_in_use") is not None else ""
            )
            + (
                f", limit {fmt_bytes(alloc.get('bytes_limit'))}"
                if alloc.get("bytes_limit") is not None else ""
            )
        )
    return "\n".join(lines)


def format_oom_text(report: dict) -> str:
    lines = [oom_summary_line(report)]
    if report.get("headline"):
        lines.append(f"  {report['headline']}")
    for b in report.get("buffers") or []:
        lines.append(
            f"  {b['rank']:>3}. {fmt_bytes(b['size_bytes']):>10}"
            + (f"  {b['shape']}" if b.get("shape") else "")
            + (f"  {b['op']}" if b.get("op") else "")
        )
    return "\n".join(lines)


# --------------------------------------------------------------------------
# History-report engine (`obs memory <run.jsonl>`).
# --------------------------------------------------------------------------


def memory_report(records: List[dict]) -> dict:
    """Fold a run's history into the memory view: the ``memory`` ledger
    records (schema v11), the per-epoch ``mem.*`` gauge series out of
    the counter snapshots, any OOM events, and the single
    ``peak_hbm_bytes`` scalar ``obs compare`` gates on."""
    ledgers: List[dict] = []
    ooms: List[dict] = []
    series: List[dict] = []
    peak: Optional[int] = None
    for rec in records:
        kind = rec.get("kind")
        if kind == "memory":
            if rec.get("event") == "oom":
                ooms.append({
                    k: rec.get(k) for k in ("epoch", "oom", "ledger")
                    if rec.get(k) is not None
                })
            else:
                ledgers.append(rec)
                p = record_peak_hbm(rec)
                if p is not None:
                    peak = max(peak or 0, p)
        cnt = rec.get("counters")
        if kind == "train_epoch" and isinstance(cnt, dict):
            row = {
                k.split("mem.", 1)[1]: v for k, v in cnt.items()
                if k.startswith("mem.") and isinstance(v, (int, float))
            }
            if row:
                row["epoch"] = rec.get("epoch")
                series.append(row)
        if isinstance(cnt, dict):
            v = cnt.get("mem.peak_bytes", cnt.get("mem.peak_bytes_in_use"))
            if isinstance(v, (int, float)) and v > 0:
                peak = max(peak or 0, int(v))
    return {
        "ledgers": ledgers,
        "ooms": ooms,
        "epoch_series": series,
        "peak_hbm_bytes": peak,
    }


def format_report_text(report: dict) -> str:
    lines: List[str] = []
    for led in report["ledgers"]:
        lines.append(format_ledger_text(led))
    if report["epoch_series"]:
        lines.append("per-epoch mem.* gauges (worst chip):")
        lines.append(
            f"  {'epoch':>5} {'in_use':>10} {'peak':>10} {'headroom':>9} "
            f"{'skew':>10}"
        )
        for row in report["epoch_series"]:
            hr = row.get("headroom_frac")
            ep = row.get("epoch")
            lines.append(
                f"  {(ep if ep is not None else '-'):>5} "
                f"{fmt_bytes(row.get('bytes_in_use')):>10} "
                f"{fmt_bytes(row.get('peak_bytes', row.get('peak_bytes_in_use'))):>10} "
                f"{(format(hr, '.1%') if isinstance(hr, (int, float)) else '-'):>9} "
                f"{fmt_bytes(row.get('bytes_in_use_skew')):>10}"
            )
    for o in report["ooms"]:
        lines.append("OOM event" + (
            f" at epoch {o['epoch']}" if o.get("epoch") is not None else ""
        ) + ":")
        if isinstance(o.get("oom"), dict):
            lines.append("  " + oom_summary_line(o["oom"]))
    if report["peak_hbm_bytes"] is not None:
        lines.append(
            f"peak HBM (compare gate scalar): "
            f"{fmt_bytes(report['peak_hbm_bytes'])} "
            f"({report['peak_hbm_bytes']} B)"
        )
    if not lines:
        lines.append("no memory telemetry in this history")
    return "\n".join(lines)
