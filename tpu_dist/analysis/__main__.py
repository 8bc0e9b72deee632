"""CLI: ``python -m tpu_dist.analysis`` — lint + jaxpr audit, gate-ready.

Exit codes: 0 clean (after suppressions + baseline), 1 violations,
2 internal error. ``--format json`` emits one machine-readable object for
the CI gate (including the full rule registry, the same source of truth
docs/analysis.md's rule table is tested against); text mode prints
``file:line:col: TDxxx message`` lines.

``python -m tpu_dist.analysis shard`` runs Layer 3 — the static HLO
sharding & collective audit (TD116/TD117) — and writes/prints the
``shard_report.json`` (docs/shard_report.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# The jaxpr layer traces shard_map programs, which need a multi-device
# mesh: force the 8-device emulated CPU backend BEFORE jax initializes
# (same mechanism as tests/conftest.py).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

from tpu_dist.analysis import baseline as baseline_lib  # noqa: E402
from tpu_dist.analysis.lint import lint_paths  # noqa: E402
from tpu_dist.analysis.rules import RULES  # noqa: E402

DEFAULT_BASELINE = "tools/analysis_baseline.json"


def shard_main(argv) -> int:
    """The ``shard`` subcommand: lower + compile every config family,
    audit the optimized HLO (TD116/TD117), emit the shard report."""
    ap = argparse.ArgumentParser(
        prog="python -m tpu_dist.analysis shard",
        description="static HLO sharding & collective audit (TD116/TD117) "
        "— writes shard_report.json",
    )
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument(
        "--out", default=None,
        help="write the schema-pinned shard_report.json here",
    )
    ap.add_argument(
        "--family", action="append",
        help="analyze only this config family (repeatable)",
    )
    ap.add_argument("--list-families", action="store_true")
    ap.add_argument(
        "--inject-reshard", action="store_true",
        help="ALSO analyze the deliberately mis-sharded ZeRO-1 probe "
        "(bad in_shardings) — its TD117 findings are expected and prove "
        "the detector is alive; exit 2 if it comes back clean",
    )
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    from tpu_dist.analysis import shardlint
    from tpu_dist.comm import mesh as mesh_lib

    if args.list_families:
        for name in shardlint.registered_families():
            print(name)
        return 0
    unknown = sorted(
        set(args.family or ()) - set(shardlint.registered_families())
    )
    if unknown:
        print(
            f"tpu_dist.analysis shard: unknown famil(ies) {unknown}; "
            f"registered: {shardlint.registered_families()}",
            file=sys.stderr,
        )
        return 2
    report, violations = shardlint.build_shard_report(names=args.family)
    if args.inject_reshard:
        inj = shardlint.injected_bad_zero1(mesh_lib.data_parallel_mesh())
        inj_report, inj_vs = shardlint.shard_case(
            "zero1_sgd", step_override=inj
        )
        report["injected_reshard_probe"] = {
            "violations": [v.to_json() for v in inj_vs],
            "caught": bool(inj_vs),
        }
        if not inj_vs:
            print(
                "tpu_dist.analysis shard: the injected bad-in_shardings "
                "probe came back CLEAN — the TD117 detector is dead",
                file=sys.stderr,
            )
            return 2
    if args.out:
        shardlint.save_shard_report(report, args.out)
    if args.format == "json":
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        print(shardlint.format_text(report))
        for v in violations:
            print(v.format_text())
        if args.out:
            print(f"shardlint: wrote {args.out}")
    if report["counts"]["skipped"] and not args.family:
        # a full run that silently skipped families must be loud (the
        # robustness contract: degrade per family, fail the gate overall)
        print(
            f"tpu_dist.analysis shard: {report['counts']['skipped']} "
            f"famil(ies) skipped: {report['skips']}",
            file=sys.stderr,
        )
        return 2
    return 1 if violations else 0


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "shard":
        return shard_main(argv[1:])
    ap = argparse.ArgumentParser(
        prog="python -m tpu_dist.analysis",
        description="distributed-training lint (TD0xx) + jaxpr audit (TD1xx)",
    )
    ap.add_argument(
        "paths",
        nargs="*",
        default=["tpu_dist"],
        help="files/dirs to lint (default: tpu_dist)",
    )
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument(
        "--baseline",
        default=None,
        help=f"baseline file (default: {DEFAULT_BASELINE} when it exists)",
    )
    ap.add_argument(
        "--write-baseline",
        action="store_true",
        help="accept current findings into the baseline file and exit 0",
    )
    ap.add_argument("--no-lint", action="store_true", help="skip the AST lint layer")
    ap.add_argument(
        "--no-jaxpr", action="store_true", help="skip the jaxpr audit layer"
    )
    ap.add_argument(
        "--case",
        action="append",
        help="run only this jaxpr audit case (repeatable)",
    )
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in sorted(RULES.values(), key=lambda r: r.id):
            print(f"{r.id}  {r.name}\n      {r.summary}")
        return 0

    violations = []
    report: dict = {}
    if not args.no_lint:
        try:
            violations.extend(lint_paths(args.paths))
        except FileNotFoundError as e:
            print(f"tpu_dist.analysis: {e}", file=sys.stderr)
            return 2
    if not args.no_jaxpr:
        import jax

        jax.config.update("jax_platforms", "cpu")
        from tpu_dist.analysis.jaxpr_audit import audit_all, registered_cases

        if args.case:
            unknown = sorted(set(args.case) - set(registered_cases()))
            if unknown:
                print(
                    f"tpu_dist.analysis: unknown audit case(s) {unknown}; "
                    f"registered: {registered_cases()}",
                    file=sys.stderr,
                )
                return 2
        jaxpr_report, jaxpr_violations = audit_all(names=args.case)
        report["jaxpr"] = jaxpr_report
        violations.extend(jaxpr_violations)

    baseline_path = args.baseline or (
        DEFAULT_BASELINE if os.path.exists(DEFAULT_BASELINE) else None
    )
    if args.write_baseline:
        if args.no_lint or args.no_jaxpr or args.case or args.paths != ["tpu_dist"]:
            # a partial run would REPLACE the file with only this run's
            # findings, silently dropping accepted entries from the layers
            # or paths that did not execute
            print(
                "tpu_dist.analysis: refusing --write-baseline on a partial "
                "run (--no-lint/--no-jaxpr/--case/custom paths); run the "
                "full analyzer to regenerate the baseline",
                file=sys.stderr,
            )
            return 2
        path = args.baseline or DEFAULT_BASELINE
        baseline_lib.write(violations, path)
        print(f"wrote {len(violations)} accepted finding(s) to {path}")
        return 0

    stale: list = []
    if baseline_path:
        violations, stale = baseline_lib.apply(
            violations, baseline_lib.load(baseline_path)
        )

    if args.format == "json":
        out = {
            "violations": [v.to_json() for v in violations],
            "stale_baseline_entries": stale,
            "jaxpr_report": report.get("jaxpr", {}),
            "counts": {"new": len(violations), "stale_baseline": len(stale)},
            # the FULL rule registry, in one machine-readable place — the
            # same source of truth docs/analysis.md's rule table is tested
            # against (tests/test_shardlint.py), so a rule cannot land
            # half-registered
            "rules": [
                {"id": r.id, "name": r.name, "summary": r.summary}
                for r in sorted(RULES.values(), key=lambda r: r.id)
            ],
        }
        print(json.dumps(out, indent=2))
    else:
        for v in violations:
            print(v.format_text())
        for e in stale:
            print(
                f"stale baseline entry (no longer produced): "
                f"{e.get('rule')} {e.get('path')} {e.get('snippet')!r}"
            )
        n = len(violations)
        print(
            f"tpu_dist.analysis: {n} new violation(s)"
            + (f", {len(stale)} stale baseline entr(ies)" if stale else "")
        )
    return 1 if violations else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except BrokenPipeError:
        sys.exit(0)  # output piped into head etc.
    except BaseException:  # noqa: BLE001 — exit 2 distinguishes tool crashes
        import traceback

        traceback.print_exc()
        sys.exit(2)
