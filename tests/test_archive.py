"""The longitudinal run archive (ISSUE 20, docs/observability.md
"Longitudinal archive & trend gating"): ingest idempotence by capture
fingerprint with stale re-emissions archived-but-excluded, torn-tail
healing, forward-compat newer-schema skip-with-count, MAD-band
arithmetic against hand math, the ``compare --against-archive`` exit
contract (0 in-band / 1 regressed / 2 when the gate compared nothing),
CUSUM changepoint localization + ``--blame``, hub snapshot records,
``bench.py --archive`` never-dies self-ingest, and the TD124 noop gate
with its vacuity guard. Everything here is host-side file arithmetic except the
TD124 jaxpr gate, which gates in the analysis.yml archive step too.
"""

import inspect
import json
import os

import pytest

THROUGHPUT = "resnet18_cifar100_train_throughput"


def _bench_rec(value, i, *, metric=THROUGHPUT, **extra):
    rec = {
        "metric": metric,
        "value": value,
        "unit": "images/sec",
        "capture": {
            "host": "testhost",
            "bench_run_id": f"run{i:02d}",
            "mono_s": float(i),
        },
    }
    rec.update(extra)
    return rec


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return str(path)


def _seed_archive(tmp_path, values, name="archive.jsonl"):
    """Ingest one fresh bench record per value and return the archive."""
    from tpu_dist.obs import archive as archive_lib

    arch = str(tmp_path / name)
    src = _write_jsonl(
        tmp_path / "seed_bench.jsonl",
        [_bench_rec(v, i) for i, v in enumerate(values)],
    )
    archive_lib.ingest_paths([src], arch)
    return arch


# -- ingest: idempotence, staleness, torn tails, forward compat --------------


def test_ingest_idempotent_by_capture_fingerprint(tmp_path):
    from tpu_dist.obs import archive as archive_lib

    arch = str(tmp_path / "archive.jsonl")
    src = _write_jsonl(
        tmp_path / "bench.jsonl",
        [_bench_rec(100.0 + i, i) for i in range(4)],
    )
    rep1 = archive_lib.ingest_paths([src], arch)
    assert rep1["appended"] == 4 and rep1["deduped"] == 0
    rep2 = archive_lib.ingest_paths([src], arch)
    assert rep2["appended"] == 0 and rep2["deduped"] == 4
    records, counts = archive_lib.load_archive(arch)
    assert len(records) == 4 and counts["bad_lines"] == 0
    # seq is monotone from 1 in archive order
    assert [r["seq"] for r in records] == [1, 2, 3, 4]
    assert all(r["schema"] == archive_lib.SCHEMA for r in records)


def test_stale_reemission_archived_flagged_and_excluded(tmp_path):
    """A re-emitted capture (bench's stale-stamped last-good fallback,
    the BENCH_r05 shape) archives as its OWN record — flagged STALE,
    fingerprint suffixed so it does not dedupe-collide with the fresh
    original — and the band is built from the fresh records only."""
    from tpu_dist.obs import archive as archive_lib

    arch = str(tmp_path / "archive.jsonl")
    fresh = [_bench_rec(100.0, 0), _bench_rec(102.0, 1)]
    reemit = dict(fresh[1], stale=True, note="re-emitted last good")
    src = _write_jsonl(tmp_path / "bench.jsonl", fresh + [reemit])
    rep = archive_lib.ingest_paths([src], arch)
    assert rep["appended"] == 3 and rep["stale_appended"] == 1
    records, _ = archive_lib.load_archive(arch)
    stale = [r for r in records if r["stale"]]
    assert len(stale) == 1
    assert ":stale:" in stale[0]["fingerprint"]
    assert stale[0]["meta"].get("reemitted_capture") is True
    band = archive_lib.band_for(records, THROUGHPUT, "value")
    assert band is not None and band["n"] == 2  # stale point excluded
    assert band["median"] == pytest.approx(101.0)
    # re-ingesting the same stream appends nothing: the fresh records
    # dedupe on their capture fingerprint and the stale copy on its
    # content-suffixed one
    rep2 = archive_lib.ingest_paths([src], arch)
    assert rep2["appended"] == 0 and rep2["deduped"] == 3


def test_byte_identical_duplicate_dedupes_not_stale(tmp_path):
    """A byte-equivalent duplicate of an archived FRESH record (same
    label, metrics, provenance) is a re-ingest — deduped, never minted
    as a spurious STALE copy. Only a re-emission that DIFFERS (the
    stale stamp, a driver round's meta) archives as a stale record."""
    from tpu_dist.obs import archive as archive_lib

    arch = str(tmp_path / "archive.jsonl")
    rec = _bench_rec(100.0, 0)
    src = _write_jsonl(tmp_path / "bench.jsonl", [rec, dict(rec)])
    rep = archive_lib.ingest_paths([src], arch)
    assert rep["appended"] == 1 and rep["deduped"] == 1
    assert rep["stale_appended"] == 0


def test_torn_tail_healed_on_append_and_counted_on_load(tmp_path):
    """A writer killed mid-line leaves a torn fragment; the next append
    isolates it on its own line and the loader counts (never crashes)."""
    from tpu_dist.obs import archive as archive_lib

    arch = _seed_archive(tmp_path, [100.0, 101.0])
    with open(arch, "a") as f:
        f.write('{"schema": "archive_record_v1", "label": "to')  # torn
    src = _write_jsonl(tmp_path / "more.jsonl", [_bench_rec(102.0, 9)])
    rep = archive_lib.ingest_paths([src], arch)
    assert rep["appended"] == 1
    records, counts = archive_lib.load_archive(arch)
    assert counts["bad_lines"] == 1
    assert len(records) == 3  # the record appended AFTER the tear is intact
    assert records[-1]["metrics"]["value"] == 102.0


def test_forward_compat_newer_schema_read_with_count(tmp_path):
    """archive_record_v2+ lines are read by their known fields and
    counted; non-archive lines are skipped with a count — the house
    additive-bump contract, never a crash."""
    from tpu_dist.obs import archive as archive_lib

    arch = _seed_archive(tmp_path, [100.0])
    with open(arch, "a") as f:
        f.write(json.dumps({
            "schema": "archive_record_v2", "label": THROUGHPUT,
            "fingerprint": "capture:future:run99:9.0", "stale": False,
            "metrics": {"value": 101.0}, "seq": 2,
            "from_the_future": {"shiny": True},
        }) + "\n")
        f.write(json.dumps({"kind": "train_epoch", "epoch": 0}) + "\n")
    records, counts = archive_lib.load_archive(arch)
    assert counts["newer_schema"] == 1 and counts["skipped_schema"] == 1
    assert len(records) == 2
    band = archive_lib.band_for(records, THROUGHPUT, "value")
    assert band["n"] == 2  # the v2 record's known fields participate


def test_ingest_unrecognized_input_is_exit_2(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    bad = tmp_path / "mystery.json"
    bad.write_text(json.dumps({"weird": "shape"}))
    arch = str(tmp_path / "archive.jsonl")
    assert obs_main(["archive", "ingest", str(bad), "-a", arch]) == 2
    assert "failed" in capsys.readouterr().err
    assert not os.path.exists(arch)  # nothing half-appended


# -- the MAD band -------------------------------------------------------------


def test_band_math_matches_hand_arithmetic(tmp_path):
    """median/MAD and the allowance against hand-computed values:
    vals = [100, 101, 102, 103, 120] -> median 102, MAD 1;
    allowed = max(k*MAD, rel_floor*|median|) + slack."""
    from tpu_dist.obs import archive as archive_lib
    from tpu_dist.obs import compare as compare_lib

    arch = _seed_archive(tmp_path, [100.0, 101.0, 102.0, 103.0, 120.0])
    records, _ = archive_lib.load_archive(arch)
    band = archive_lib.band_for(records, THROUGHPUT, "value")
    assert band["n"] == 5
    assert band["median"] == pytest.approx(102.0)
    # |v - 102| = [2, 1, 0, 1, 18] -> median 1
    assert band["mad"] == pytest.approx(1.0)
    _direction, slack = compare_lib.direction_of("value")
    row = archive_lib._gate_row(
        "value", THROUGHPUT, "value", 96.0, records,
        k=3.0, window=20, rel_floor=0.05,
    )
    # max(3*1.0, 0.05*102) = 5.1 (+ slack); 102 - 96 = 6 > 5.1 -> REGRESSED
    assert row["allowed"] == pytest.approx(max(3.0, 5.1) + slack)
    assert row["verdict"] == "REGRESSED"
    ok = archive_lib._gate_row(
        "value", THROUGHPUT, "value", 97.0, records,
        k=3.0, window=20, rel_floor=0.05,
    )
    assert ok["verdict"] == ("ok" if slack >= 0.0 else "REGRESSED")
    assert ok["verdict"] == "ok"  # 102 - 97 = 5 < 5.1


def test_band_window_keeps_trailing_records(tmp_path):
    from tpu_dist.obs import archive as archive_lib

    arch = _seed_archive(tmp_path, [50.0] * 10 + [100.0] * 5)
    records, _ = archive_lib.load_archive(arch)
    band = archive_lib.band_for(records, THROUGHPUT, "value", window=5)
    assert band["n"] == 5 and band["median"] == pytest.approx(100.0)


# -- the gate exit contract ---------------------------------------------------


def test_gate_exit_contract_0_in_band_1_regressed(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    arch = _seed_archive(tmp_path, [100.0, 100.5, 99.5, 100.2, 99.8])
    same = _write_jsonl(tmp_path / "same.jsonl", [_bench_rec(100.1, 50)])
    worse = _write_jsonl(tmp_path / "worse.jsonl", [_bench_rec(90.0, 51)])
    better = _write_jsonl(
        tmp_path / "better.jsonl", [_bench_rec(120.0, 52)]
    )
    assert obs_main(
        ["compare", same, "--against-archive", arch, "--bench"]
    ) == 0
    assert obs_main(
        ["compare", worse, "--against-archive", arch, "--bench"]
    ) == 1
    # better than the band is NEVER flagged (direction-aware)
    assert obs_main(
        ["compare", better, "--against-archive", arch, "--bench"]
    ) == 0
    out = capsys.readouterr().out
    assert "REGRESSED" in out and "archive gate" in out


def test_gate_all_stale_compares_nothing_exits_2(tmp_path, capsys):
    """When every archived point for the candidate's metrics is a stale
    re-emission there is no band; the gate compared nothing and must
    exit 2, never silently pass — the exact r03-r05 wound."""
    from tpu_dist.obs import archive as archive_lib
    from tpu_dist.obs.__main__ import main as obs_main

    arch = str(tmp_path / "archive.jsonl")
    src = _write_jsonl(
        tmp_path / "stale.jsonl", [_bench_rec(100.0, 0, stale=True)]
    )
    rep = archive_lib.ingest_paths([src], arch)
    assert rep["stale_appended"] == 1
    cand = _write_jsonl(tmp_path / "cand.jsonl", [_bench_rec(100.0, 9)])
    assert obs_main(
        ["compare", cand, "--against-archive", arch, "--bench"]
    ) == 2
    assert "compared nothing" in capsys.readouterr().err


def test_gate_stale_candidate_is_flagged_not_compared(tmp_path, capsys):
    """A candidate that re-emits an ARCHIVED capture fingerprint is a
    stale copy: its row reads STALE and contributes nothing."""
    from tpu_dist.obs.__main__ import main as obs_main

    arch = _seed_archive(tmp_path, [100.0, 100.5, 99.5])
    # re-emit archived capture 1 (bench_run_id run01 / mono_s 1.0)
    cand = _write_jsonl(tmp_path / "cand.jsonl", [_bench_rec(100.5, 1)])
    assert obs_main(
        ["compare", cand, "--against-archive", arch, "--bench",
         "--format", "json"]
    ) == 2
    out = capsys.readouterr().out
    result = json.loads(out[out.index("{"):])
    assert result["stale"] == 1 and result["compared"] == 0


def test_gate_bad_invocations_exit_2(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    arch = _seed_archive(tmp_path, [100.0])
    cand = _write_jsonl(tmp_path / "c.jsonl", [_bench_rec(100.0, 9)])
    # two positionals with --against-archive: the archive IS the baseline
    assert obs_main(
        ["compare", cand, cand, "--against-archive", arch, "--bench"]
    ) == 2
    # empty archive: a gate with no history is broken, not passing
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert obs_main(
        ["compare", cand, "--against-archive", empty, "--bench"]
    ) == 2
    # --band-k without --against-archive is a contract violation
    assert obs_main(["compare", cand, cand, "--band-k", "2.0"]) == 2
    capsys.readouterr()


def test_gate_band_k_widens_the_band(tmp_path):
    from tpu_dist.obs.__main__ import main as obs_main

    arch = _seed_archive(tmp_path, [100.0, 101.0, 102.0, 103.0, 104.0])
    cand = _write_jsonl(tmp_path / "c.jsonl", [_bench_rec(93.0, 9)])
    args = ["compare", cand, "--against-archive", arch, "--bench"]
    assert obs_main(args + ["--band-k", "3.0"]) == 1
    assert obs_main(args + ["--band-k", "12.0"]) == 0


# -- trend + changepoint blame ------------------------------------------------


def test_changepoint_localizes_injected_step(tmp_path):
    from tpu_dist.obs import archive as archive_lib

    values = [100.0, 100.2, 99.8, 100.1, 99.9, 100.0,
              90.0, 90.2, 89.8, 90.1]
    arch = _seed_archive(tmp_path, values)
    records, _ = archive_lib.load_archive(arch)
    report = archive_lib.trend_report(records, metric="value")
    (series,) = [s for s in report["series"] if s["metric"] == "value"]
    cp = series["changepoint"]
    assert cp is not None and cp["index"] == 6
    assert cp["kind"] == "regressed"  # throughput stepped DOWN
    assert cp["blame"]["fingerprint"] == "capture:testhost:run06:6.0"
    assert cp["before_mean"] == pytest.approx(100.0, abs=0.1)
    assert cp["after_mean"] == pytest.approx(90.0, abs=0.2)


def test_changepoint_flat_series_never_flags(tmp_path):
    """Float dust on a flat series must not flag (the rel_min floor)."""
    from tpu_dist.obs import archive as archive_lib

    vals = [100.0 + 0.001 * ((-1) ** i) for i in range(12)]
    arch = _seed_archive(tmp_path, vals)
    records, _ = archive_lib.load_archive(arch)
    report = archive_lib.trend_report(records, metric="value")
    (series,) = [s for s in report["series"] if s["metric"] == "value"]
    assert series["changepoint"] is None


def test_trend_cli_blame_names_the_record(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    values = [100.0, 100.2, 99.8, 100.1, 99.9, 100.0,
              90.0, 90.2, 89.8, 90.1]
    arch = _seed_archive(tmp_path, values)
    assert obs_main(["trend", arch, "--blame"]) == 0
    out = capsys.readouterr().out
    assert "changepoint [regressed]" in out
    assert "blame: first shifted record is fingerprint " \
        "capture:testhost:run06:6.0" in out
    # empty archive: nothing to trend -> exit 1
    empty = str(tmp_path / "none.jsonl")
    open(empty, "w").close()
    assert obs_main(["trend", empty]) == 1
    capsys.readouterr()


def test_trend_stale_only_metric_renders_counted_not_empty(tmp_path):
    from tpu_dist.obs import archive as archive_lib

    arch = str(tmp_path / "archive.jsonl")
    src = _write_jsonl(
        tmp_path / "stale.jsonl", [_bench_rec(100.0, 0, stale=True)]
    )
    archive_lib.ingest_paths([src], arch)
    records, _ = archive_lib.load_archive(arch)
    report = archive_lib.trend_report(records)
    (series,) = [s for s in report["series"] if s["metric"] == "value"]
    assert series["n"] == 0 and series["n_stale"] == 1
    text = archive_lib.format_trend_text(report)
    assert "+1 STALE excluded" in text


# -- the TD124 injected-fault probe -------------------------------------------


def test_inject_regression_probe_catches_and_localizes(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    arch = _seed_archive(tmp_path, [100.0, 100.5, 99.5, 100.2, 99.8])
    assert obs_main(
        ["trend", arch, "--inject-regression", "--format", "json"]
    ) == 0
    out = capsys.readouterr().out
    probe = json.loads(out[out.index("{"):])
    assert probe["gate_probe"] == "caught"
    assert probe["improvements_clean"] is True
    assert probe["changepoint_probe"] == "localized"
    assert probe["bands_probed"] >= 1
    assert all(g["caught"] for g in probe["gate_results"])


def test_dead_detector_exits_2(tmp_path, capsys, monkeypatch):
    """Gut the band gate so the injected regression comes back unflagged:
    the probe must report DEAD and the CLI must exit 2 (TD124)."""
    from tpu_dist.obs import archive as archive_lib
    from tpu_dist.obs.__main__ import main as obs_main

    arch = _seed_archive(tmp_path, [100.0, 100.5, 99.5, 100.2, 99.8])
    real_row = archive_lib._gate_row

    def lobotomized(*args, **kw):
        row = real_row(*args, **kw)
        if row.get("verdict") == "REGRESSED":
            row["verdict"] = "ok"
        return row

    monkeypatch.setattr(archive_lib, "_gate_row", lobotomized)
    assert obs_main(["trend", arch, "--inject-regression"]) == 2
    assert "dead" in capsys.readouterr().err
    # the library-level verdict agrees
    records, _ = archive_lib.load_archive(arch)
    assert archive_lib.probe_is_dead(archive_lib.inject_probe(records))


# -- TD124: registered, gated, vacuity-guarded --------------------------------


def test_td124_registered_and_audit_all_wired():
    from tpu_dist.analysis import jaxpr_audit
    from tpu_dist.analysis.rules import RULES

    assert "TD124" in RULES
    assert RULES["TD124"].name == "archive-gate-not-vacuous"
    assert "archive_gate_noop_violations" in inspect.getsource(
        jaxpr_audit.audit_all
    )


def test_td124_gate_archive_kit_is_noop():
    from tpu_dist.analysis.jaxpr_audit import archive_gate_noop_violations

    assert archive_gate_noop_violations() == []


def test_td124_probe_is_vacuity_guarded(monkeypatch):
    """A probe whose detector went dead must REPORT, not pass — gut
    probe_is_dead's input by making the gate miss everything."""
    from tpu_dist.analysis.jaxpr_audit import archive_gate_noop_violations
    from tpu_dist.obs import archive as archive_lib

    monkeypatch.setattr(
        archive_lib, "probe_is_dead", lambda probe: True
    )
    vs = archive_gate_noop_violations()
    assert len(vs) == 1 and vs[0].rule == "TD124"
    assert "VACUOUS" in vs[0].message or "dead" in vs[0].message


# -- satellites: hub records, bench self-ingest, stamp -----------------------


def test_hub_snapshot_record_and_append(tmp_path):
    from tpu_dist.obs import archive as archive_lib

    snapshot = {
        "scrapes": 3,
        "drops": 1,
        "rollup": {
            "runs_aggregated": 2, "runs_dead": 1, "breach_count": 2,
            "total_chips": 8, "worst_stall_frac": 0.25,
            "goodput_by_kind": {"train": 0.9, "serve": 0.97},
        },
    }
    arch = str(tmp_path / "hub_archive.jsonl")
    rec = archive_lib.append_hub_snapshot(arch, snapshot, now=123.0)
    assert rec["label"] == "pod" and rec["source"] == "hub"
    assert rec["metrics"] == {
        "pod_runs_dead": 1, "pod_breach_count": 2, "pod_total_chips": 8,
        "pod_worst_stall_frac": 0.25, "pod_goodput_frac_train": 0.9,
        "pod_goodput_frac_serve": 0.97,
    }
    assert rec["fingerprint"].startswith("hub:")
    assert rec["meta"]["runs_aggregated"] == 2
    # a second interval appends (distinct fingerprint), never collides
    snapshot["scrapes"] = 4
    archive_lib.append_hub_snapshot(arch, snapshot, now=124.0)
    records, _ = archive_lib.load_archive(arch)
    assert len(records) == 2 and records[1]["seq"] == 2
    # every hub metric has a registered direction (gateable)
    from tpu_dist.obs import compare as compare_lib

    for name in rec["metrics"]:
        assert compare_lib.direction_of(name)


def test_bench_self_ingest_never_dies(tmp_path, capsys):
    """bench.py --archive: records emitted through _stamped self-ingest
    at exit; an unwritable archive warns and NEVER raises (a perf probe
    must not die on its bookkeeping)."""
    import bench

    rec = {"metric": "synthetic", "value": 1.0}
    arch = str(tmp_path / "bench_archive.jsonl")
    bench._self_ingest(arch, [_bench_rec(100.0, 0)])
    from tpu_dist.obs import archive as archive_lib

    records, _ = archive_lib.load_archive(arch)
    assert len(records) == 1 and records[0]["source_path"] == "bench.py"
    # a directory path cannot be appended to: warn, don't raise
    bench._self_ingest(str(tmp_path), [rec])
    err = capsys.readouterr().err
    assert "archive" in err
    # _stamped feeds the module-level emission list the atexit hook reads
    before = len(bench._EMITTED)
    bench._stamped(dict(rec))
    assert len(bench._EMITTED) == before + 1
    bench._EMITTED.pop()


def test_summarize_json_stamps_capture_fingerprint(tmp_path, capsys):
    """`obs summarize --format json` stamps the content-based capture
    identity + source log path that archive ingest dedupes by."""
    from tpu_dist.obs import summarize as summ
    from tpu_dist.obs.__main__ import main as obs_main

    log = _write_jsonl(tmp_path / "run.jsonl", [{
        "kind": "train_epoch", "epoch": 0, "run_id": "r1", "loss": 2.0,
        "epoch_time": 2.0, "images_per_sec": 1000.0,
        "step_time_p50": 0.01, "step_time_p95": 0.02,
        "step_time_p99": 0.03, "data_stall_frac": 0.05,
    }])
    assert obs_main(["summarize", log, "--format", "json"]) == 0
    out = capsys.readouterr().out
    report = json.loads(out)
    assert report["capture"]["fingerprint"] == \
        summ.capture_stamp(log)["fingerprint"]
    assert report["capture"]["run_id"] == "r1"
    assert report["source_log"] == os.path.abspath(log)
    # content-based: a byte-identical copy fingerprints identically
    copy = str(tmp_path / "copy.jsonl")
    with open(log) as src, open(copy, "w") as dst:
        dst.write(src.read())
    assert summ.capture_stamp(copy)["fingerprint"] == \
        report["capture"]["fingerprint"]


def test_history_log_ingests_and_gates(tmp_path):
    """A --log_file history archives one record over its summarize
    scalars (label `history`) and a worse candidate history regresses
    against the band."""
    from tpu_dist.obs import archive as archive_lib
    from tpu_dist.obs.__main__ import main as obs_main

    def _hist(path, ips):
        return _write_jsonl(path, [{
            "kind": "train_epoch", "epoch": e, "run_id": "r", "loss": 2.0,
            "epoch_time": 2.0, "images_per_sec": ips,
            "step_time_p50": 0.01, "step_time_p95": 0.02,
            "step_time_p99": 0.03, "data_stall_frac": 0.05,
        } for e in range(2)])

    arch = str(tmp_path / "archive.jsonl")
    for i, ips in enumerate([1000.0, 1010.0, 990.0]):
        src = _hist(tmp_path / f"h{i}.jsonl", ips)
        rep = archive_lib.ingest_paths([src], arch)
        assert rep["appended"] == 1
    records, _ = archive_lib.load_archive(arch)
    assert all(r["label"] == "history" for r in records)
    assert records[0]["fingerprint"].startswith("history:")
    worse = _hist(tmp_path / "worse.jsonl", 600.0)
    assert obs_main(["compare", worse, "--against-archive", arch]) == 1
    same = _hist(tmp_path / "same.jsonl", 1000.0)
    assert obs_main(["compare", same, "--against-archive", arch]) == 0
