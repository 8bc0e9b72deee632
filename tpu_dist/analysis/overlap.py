"""Comm/compute overlap autotuner (Layer 4b, ``tune-overlap``) — search
the collective-*scheduling* knobs the step builders already expose and
pick, per config family, the setting that maximizes the measured overlap
headroom. The knobs move WHEN collectives run, never WHAT they carry:

- ``pmean_fusion`` (``dp_sgd``): one fused multi-operand grad pmean vs
  one pmean per gradient leaf — same payload bytes, many small
  collectives the scheduler can launch as each leaf's backward finishes.
- ``quant_chunk`` (``dp_int8`` / ``dp_int8_ef``): the int8 wire's
  quantization-block size — payload bytes identical, only the f32 scale
  sideband (and the chunking of the two all-to-all legs) changes.
- ``rs_ag_chunks`` (``zero1_sgd``): split the ZeRO-1 reduce-scatter /
  all-gather pair into k pipelined column-group collectives — the groups
  tile the padded extent exactly, so not one wire byte is added.

TD121 pins that contract mechanically, per candidate: the shardlint
payload bucket (``hlo_wire_buckets``) must be byte-identical to the
family's baseline, and the schedule metric must MOVE (a knob that
changes nothing is a lying search space). The ``--inject-payload`` probe
perturbs a recorded payload and requires the detector to fire — clean
means the detector is dead, CLI exit 2, the same acceptance discipline
as the planner's ``--inject-miscost`` (TD118).

Overlap measurement: with a profiler capture (``jax.profiler`` +
``obs/xprof.py``) the real ``overlap_frac`` is the objective. Without a
chip the static proxy is the compiled-HLO *scheduling
distance* — for every collective, how many instructions sit between it
and its first consumer in the optimized module. XLA's async pairs make
this literal (the ``-start``→``-done`` gap IS the overlap window); for
sync ops it measures how much independent work the scheduler placed
behind the op. Deterministic, pure-compile, no devices harmed.

The emitted ``tune_report.json`` (schema ``tune_report_v1``) is consumed
by the ``--auto_shard`` planner (``planner.build_plan(tune_report=...)``)
which attaches the chosen knobs to its chosen family, and by the trainer,
which applies them and exports ``tune.*`` gauges into history.
"""

from __future__ import annotations

import copy
import json
import re
from typing import Optional

from tpu_dist.analysis.rules import Violation

SCHEMA = "tune_report_v1"
SCHEMA_VERSION = 1
_SCHEMA_RE = re.compile(r"^tune_report_v(\d+)$")


class TuneReportError(ValueError):
    """A tune_report.json failed schema validation on load."""


# --------------------------------------------------------------------------
# The knob space. Baseline ({}) first — every candidate is judged against
# it. Values are make_train_step kwargs, plain data (serializable).
# --------------------------------------------------------------------------

#: The quant_chunk values are sized to the audit proxy model (the
#: _AuditMLP's per-replica row is 480/8 = 60 elements): every searched
#: value must change the scale-sideband granularity ON THE PROXY or the
#: TD121 moved-gate correctly flags it as vacuous. The report records
#: what was searched — consumers apply the chosen VALUE, and a family
#: whose baseline wins simply ships no override.
KNOB_SPACE: dict = {
    "dp_sgd": [{}, {"pmean_fusion": "per_leaf"}],
    "dp_int8": [{}, {"quant_chunk": 16}, {"quant_chunk": 32}],
    "dp_int8_ef": [{}, {"quant_chunk": 16}, {"quant_chunk": 32}],
    "zero1_sgd": [{}, {"rs_ag_chunks": 2}, {"rs_ag_chunks": 4}],
}


def tunable_families() -> list:
    return sorted(KNOB_SPACE)


# --------------------------------------------------------------------------
# The schedule metric (the CPU-valid overlap proxy)
# --------------------------------------------------------------------------

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_COLLECTIVE_DEF_RE = re.compile(
    r"=\s*.*?\s(?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\("
)


def schedule_distances(hlo_text: str) -> list:
    """Per-collective first-consumer distances from optimized HLO text.

    For every collective definition (sync op or async ``-start``), the
    number of instruction lines between it and the first later line in
    the same computation that references its result. ``-done`` ops are
    not collectives of their own — they ARE the consumer that closes a
    ``-start``'s window. A collective whose result is never referenced
    again in its computation (it is the ROOT) scores the distance to the
    computation's end — nothing can be scheduled behind it.

    Returns ``[{"computation", "line", "kind", "distance"}, ...]`` in
    module order. Deterministic for a fixed compile."""
    from tpu_dist.analysis.shardlint import _KIND_RE, _split_computations

    out = []
    for comp, lines in _split_computations(hlo_text).items():
        for i, line in enumerate(lines):
            m = _KIND_RE.search(line)
            if not m or m.group(3) == "-done":
                continue
            d = _DEF_RE.match(line)
            if not d:
                continue
            name = d.group(1)
            # %name followed by a non-identifier char, so %ar.1 does not
            # match inside %ar.12
            use_re = re.compile(r"%" + re.escape(name) + r"(?![\w.\-])")
            distance = len(lines) - 1 - i  # ROOT / never-consumed default
            for j in range(i + 1, len(lines)):
                if use_re.search(lines[j]):
                    distance = j - i
                    break
            out.append({
                "computation": comp,
                "line": i,
                "kind": m.group(2) + (m.group(3) or ""),
                "distance": distance,
            })
    return out


def schedule_metric(hlo_text: str) -> dict:
    """Aggregate :func:`schedule_distances` into the tuner's objective:
    ``mean_distance`` (higher = more independent work the scheduler
    placed behind each collective = more overlap headroom)."""
    ds = schedule_distances(hlo_text)
    n = len(ds)
    total = sum(d["distance"] for d in ds)
    return {
        "collectives": n,
        "total_distance": total,
        "mean_distance": (total / n) if n else 0.0,
        "min_distance": min((d["distance"] for d in ds), default=0),
        "per_op": ds,
    }


def overlap_frac_from_capture(capture_dir: str) -> Optional[float]:
    """Measured comm/compute ``overlap_frac`` from a ``jax.profiler``
    capture (``obs/xprof.py``) — the objective when real device traces
    exist. Returns None when the capture is unreadable (the caller falls
    back to the HLO schedule proxy, counted in the report)."""
    try:
        from tpu_dist.obs import xprof as xprof_lib

        report = xprof_lib.analyze_capture(capture_dir)
        return float(report["overlap"]["overlap_frac"])
    except Exception:
        return None


# --------------------------------------------------------------------------
# Candidate compilation + measurement
# --------------------------------------------------------------------------


def compile_candidate(family: str, knobs: dict, mesh=None) -> dict:
    """Build the family's step with ``knobs`` overriding its
    :func:`family_step_kwargs`, compile it, and measure: the shardlint
    payload/sideband wire buckets (the TD121-pinned inventory) plus the
    schedule metric. Pure compile — nothing executes."""
    from tpu_dist.analysis.jaxpr_audit import _dp_setup
    from tpu_dist.analysis.shardlint import (
        hlo_wire_buckets,
        parse_hlo_collectives,
    )
    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.obs import costmodel
    from tpu_dist.train.step import family_step_kwargs

    from tpu_dist.analysis.jaxpr_audit import trace_counts

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    kwargs = dict(family_step_kwargs(family))
    kwargs.update(knobs)
    step, args = _dp_setup(m, **kwargs)
    _, compiled = costmodel.lower_and_compile(step, *args)
    text = compiled.as_text()
    ops = parse_hlo_collectives(text)
    metric = schedule_metric(text)
    distances = [d["distance"] for d in metric.pop("per_op")]
    # the jaxpr collective-eqn count is part of the schedule fingerprint:
    # fused-vs-per-leaf pmean compiles to identical CPU HLO (XLA splits
    # the multi-operand reduce either way), but the ISSUED granularity —
    # what the TPU all-reduce combiner and latency-hiding scheduler
    # actually receive — is the eqn structure, and the knob must move it
    jaxpr_colls = sum(trace_counts(step, *args)["collectives"].values())
    return {
        "family": family,
        "knobs": dict(knobs),
        "wire": hlo_wire_buckets(ops),
        "collective_ops": len(ops),
        "jaxpr_collectives": int(jaxpr_colls),
        "fingerprint": [[op.kind, op.dtype, op.elems] for op in ops],
        "distances": distances,
        "schedule": metric,
    }


def _payload_key(entry: dict) -> tuple:
    w = entry.get("wire") or {}
    return (
        int(w.get("payload_bytes", -1)),
        int(w.get("quantized_payload_bytes", -1)),
    )


def check_candidate(
    family: str, baseline: dict, cand: dict
) -> list[Violation]:
    """The TD121 gate for one measured candidate against its family
    baseline: payload bucket byte-identical, schedule metric moved."""
    out: list[Violation] = []
    if not cand.get("knobs"):
        return out  # the baseline is its own reference
    where = f"<tune:{family}:{json.dumps(cand['knobs'], sort_keys=True)}>"
    if _payload_key(cand) != _payload_key(baseline):
        out.append(Violation(
            rule="TD121", path=where, line=0,
            message=(
                "knob changed the payload-byte inventory: baseline "
                f"payload={baseline.get('wire', {}).get('payload')} vs "
                f"candidate payload={cand.get('wire', {}).get('payload')} "
                "— tuner knobs must be schedule-only transforms"
            ),
        ))
    moved = (
        cand.get("fingerprint") != baseline.get("fingerprint")
        or cand.get("distances") != baseline.get("distances")
        or cand.get("jaxpr_collectives") != baseline.get("jaxpr_collectives")
    )
    if not moved:
        out.append(Violation(
            rule="TD121", path=where, line=0,
            message=(
                "knob did not move the collective schedule (identical "
                "HLO op sequence, first-consumer distances, and jaxpr "
                "collective-eqn structure) — a vacuous knob poisons "
                "the search space"
            ),
        ))
    return out


# --------------------------------------------------------------------------
# The search
# --------------------------------------------------------------------------


def tune(
    mesh=None, names=None, capture_dir: Optional[str] = None
) -> tuple[dict, list[Violation]]:
    """Compile every candidate in :data:`KNOB_SPACE` (restricted to
    ``names`` when given), gate each through TD121, and choose per
    family the TD121-clean candidate with the highest objective —
    measured ``overlap_frac`` when ``capture_dir`` yields one, the HLO
    schedule proxy otherwise. Build/compile failures are counted in
    ``skips``, never silent (a skipped family is CLI exit 2)."""
    import jax

    from tpu_dist.comm import mesh as mesh_lib

    m = mesh if mesh is not None else mesh_lib.data_parallel_mesh()
    fams = list(names) if names else tunable_families()
    measured_frac = (
        overlap_frac_from_capture(capture_dir) if capture_dir else None
    )
    families: dict = {}
    skips: dict = {}
    violations: list[Violation] = []
    for fam in fams:
        if fam not in KNOB_SPACE:
            skips[fam] = (
                f"no tunable knobs registered; tunable: {tunable_families()}"
            )
            continue
        space = KNOB_SPACE[fam]
        try:
            baseline = compile_candidate(fam, space[0], m)
        except Exception as e:
            skips[fam] = f"{type(e).__name__}: {e}"
            continue
        cands = [baseline]
        for knobs in space[1:]:
            try:
                cand = compile_candidate(fam, knobs, m)
            except Exception as e:
                skips[f"{fam}:{json.dumps(knobs, sort_keys=True)}"] = (
                    f"{type(e).__name__}: {e}"
                )
                continue
            vs = check_candidate(fam, baseline, cand)
            cand["td121"] = {
                "clean": not vs,
                "violations": [v.to_json() for v in vs],
            }
            violations.extend(vs)
            cands.append(cand)
        # deterministic choice: highest mean first-consumer distance
        # among TD121-clean candidates; the serialized knobs break exact
        # ties (never dict order)
        eligible = [
            c for c in cands
            if not c.get("knobs") or c.get("td121", {}).get("clean")
        ]
        chosen = max(
            eligible,
            key=lambda c: (
                c["schedule"]["mean_distance"],
                json.dumps(c["knobs"], sort_keys=True),
            ),
        )
        families[fam] = {
            "baseline": baseline,
            "candidates": cands,
            "chosen": {
                "knobs": chosen["knobs"],
                "schedule": chosen["schedule"],
                "gain_frac": (
                    chosen["schedule"]["mean_distance"]
                    / baseline["schedule"]["mean_distance"] - 1.0
                    if baseline["schedule"]["mean_distance"] else 0.0
                ),
            },
        }
    dev = jax.devices()[0]
    report = {
        "schema": SCHEMA,
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": int(m.devices.size),
        "jax_version": jax.__version__,
        "objective": (
            "xprof_overlap_frac" if measured_frac is not None
            else "hlo_schedule_proxy"
        ),
        "measured_overlap_frac": measured_frac,
        "families": families,
        "skips": skips,
        "counts": {
            "families": len(families),
            "skipped": len(skips),
            "violations": len(violations),
        },
    }
    return report, violations


def chosen_knobs(report: dict, family: str) -> dict:
    """The tuner's chosen knob dict for ``family`` (``{}`` when the
    family was not tuned / the baseline won) — the planner/trainer
    consumption hook."""
    entry = (report.get("families") or {}).get(family) or {}
    return dict((entry.get("chosen") or {}).get("knobs") or {})


# --------------------------------------------------------------------------
# TD121 acceptance probe
# --------------------------------------------------------------------------


def inject_payload(report: dict) -> dict:
    """The TD121 acceptance probe (the planner's ``inject_miscost``
    twin): a deep copy of ``report`` where every non-baseline
    candidate's recorded payload bytes are deterministically perturbed
    (doubled + 1). :func:`recheck_report` over the result MUST flag
    TD121 — a clean verdict means the detector is dead (CLI exit 2)."""
    out = copy.deepcopy(report)
    for entry in (out.get("families") or {}).values():
        for cand in entry.get("candidates") or []:
            if not cand.get("knobs"):
                continue
            w = cand.setdefault("wire", {})
            w["payload_bytes"] = int(w.get("payload_bytes", 0)) * 2 + 1
    return out


def recheck_report(report: dict) -> list[Violation]:
    """Re-run the TD121 gate over a report's RECORDED inventories (no
    recompile — this is the probe verifier and the cheap CI re-gate)."""
    out: list[Violation] = []
    for fam, entry in (report.get("families") or {}).items():
        baseline = entry.get("baseline") or {}
        for cand in entry.get("candidates") or []:
            out.extend(check_candidate(fam, baseline, cand))
    return out


# --------------------------------------------------------------------------
# tune_report.json — save / load (forward-compat), rendering
# --------------------------------------------------------------------------

_REQUIRED_CHOSEN_KEYS = ("knobs", "schedule")


def save_tune_report(report: dict, path: str) -> None:
    import os

    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def load_tune_report(path: str) -> dict:
    """Schema-pinned loader with the planner's forward-compat
    discipline: the tag must parse as ``tune_report_v<N>``; a NEWER
    version is tolerated — family entries missing the v1 keys are
    skipped with a count into ``load_notes`` — while a foreign tag, an
    older-than-supported version, or a same-version entry missing
    required keys raises the typed :class:`TuneReportError`."""
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise TuneReportError(f"{path}: not a JSON object")
    tag = data.get("schema")
    m = _SCHEMA_RE.match(tag) if isinstance(tag, str) else None
    if not m:
        raise TuneReportError(
            f"{path}: schema {tag!r} is not a tune_report tag — "
            "regenerate with `make tune-overlap`"
        )
    ver = int(m.group(1))
    if ver < SCHEMA_VERSION:
        raise TuneReportError(
            f"{path}: schema {tag!r} predates v{SCHEMA_VERSION} — "
            "regenerate with `make tune-overlap`"
        )
    newer = ver > SCHEMA_VERSION
    fams = data.get("families")
    if not isinstance(fams, dict):
        raise TuneReportError(f"{path}: no 'families' mapping")
    skipped: dict = {}
    kept: dict = {}
    for fam, entry in fams.items():
        chosen = entry.get("chosen") if isinstance(entry, dict) else None
        missing = (
            [k for k in _REQUIRED_CHOSEN_KEYS if k not in chosen]
            if isinstance(chosen, dict) else list(_REQUIRED_CHOSEN_KEYS)
        )
        if not missing:
            kept[fam] = entry
            continue
        if not newer:
            raise TuneReportError(
                f"{path}: family {fam!r} chosen entry is missing {missing}"
            )
        skipped[fam] = missing
    data["families"] = kept
    if newer:
        data["load_notes"] = {
            "newer_schema": tag,
            "reader_version": SCHEMA_VERSION,
            "skipped_families": skipped,
            "skipped_count": len(skipped),
        }
    return data


def format_text(report: dict) -> str:
    lines = [
        f"tune-overlap [{report.get('schema')}] "
        f"backend={report.get('backend')} "
        f"n_devices={report.get('n_devices')} "
        f"objective={report.get('objective')}",
    ]
    for fam, entry in sorted((report.get("families") or {}).items()):
        chosen = entry.get("chosen") or {}
        base = (entry.get("baseline") or {}).get("schedule") or {}
        lines.append(
            f"  {fam}: chosen={json.dumps(chosen.get('knobs'), sort_keys=True)} "
            f"mean_dist {base.get('mean_distance', 0):.2f} -> "
            f"{(chosen.get('schedule') or {}).get('mean_distance', 0):.2f} "
            f"({chosen.get('gain_frac', 0.0):+.1%})"
        )
        for cand in entry.get("candidates") or []:
            if not cand.get("knobs"):
                continue
            td = cand.get("td121") or {}
            tag = "ok" if td.get("clean") else "TD121-VIOLATION"
            lines.append(
                f"    cand {json.dumps(cand['knobs'], sort_keys=True)}: "
                f"mean_dist={cand['schedule']['mean_distance']:.2f} "
                f"payload={_payload_key(cand)[0]}B [{tag}]"
            )
    for key, why in sorted((report.get("skips") or {}).items()):
        lines.append(f"  SKIP {key}: {why}")
    c = report.get("counts") or {}
    lines.append(
        f"  families={c.get('families')} skipped={c.get('skipped')} "
        f"violations={c.get('violations')}"
    )
    return "\n".join(lines)
