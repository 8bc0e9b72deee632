"""Live telemetry: OpenMetrics export, alert rules, `obs tail`, TD109.

The live half of ``tpu_dist/obs`` (docs/observability.md "Live export"):

* exposition rendering against a strict OpenMetrics line grammar,
* atomic textfile publication (no torn exposition ever observable),
* the rank-0-only HTTP ``/metrics`` endpoint under concurrent scrapes,
* the alert engine's sustain / cooldown / delta state machine and the
  TOML/JSON spec loader (builtin library included),
* ``obs tail`` golden against a recorded JSONL + the torn-tail follower,
* heartbeat torn-read hardening (NFS atomic-replace races),
* bench capture fingerprints: ``compare --bench`` / ``summarize
  --bench`` flag byte-identical re-emitted captures as STALE,
* the TD109 jaxpr gate: exporter + alert engine armed ⇒ traced step
  byte-identical,
* e2e acceptance (slow): a live run scraped mid-flight — counter values
  match the JSONL for the same epoch window, a stall_frac rule fires an
  ``alert`` record + ``alert_active`` gauge in-run.
"""

import io
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from tpu_dist.obs import alerts as alerts_lib
from tpu_dist.obs import counters
from tpu_dist.obs import export as export_lib
from tpu_dist.obs.export import MetricsExporter

_HERE = os.path.dirname(__file__)
_REPO_ROOT = os.path.dirname(os.path.abspath(_HERE))


# -- OpenMetrics rendering ---------------------------------------------------

# strict line grammar: TYPE declarations, samples (bare or one-label), EOF
_TYPE_RE = re.compile(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* gauge$")
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"\})?'
    r" -?[0-9].*$"
)


def _assert_valid_exposition(text: str):
    lines = text.splitlines()
    assert lines, "empty exposition"
    assert lines[-1] == "# EOF", f"missing # EOF terminator: {lines[-3:]}"
    assert text.endswith("# EOF\n")
    declared = set()
    for line in lines[:-1]:
        if line.startswith("# TYPE "):
            assert _TYPE_RE.match(line), f"bad TYPE line: {line!r}"
            declared.add(line.split()[2])
            continue
        assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"
        name = line.split("{", 1)[0].split(" ", 1)[0]
        assert name in declared, f"sample before its TYPE: {line!r}"
        value = line.rsplit(" ", 1)[1]
        float(value)  # must parse


def test_render_passes_strict_line_grammar():
    text = export_lib.render(
        {
            "train.steps": 42,
            "train.images_per_sec": 1234.5,
            "loader.data_wait_s": 0.25,
            "ckpt.bytes_written": 10_000_000,
        },
        {"alert_active": {"stall_high": 1.0, "mfu_low": 0.0}},
    )
    _assert_valid_exposition(text)


def test_render_skips_non_numeric_and_sanitizes_names():
    text = export_lib.render({
        "run.id": "abc-123",          # info gauge: not a number → skipped
        "run.grad_compression": "int8",
        "train.steps": 3,
        "weird name!": 1,
    })
    _assert_valid_exposition(text)
    assert "abc-123" not in text and "int8" not in text
    vals = export_lib.parse(text)
    assert vals[export_lib.metric_name("train.steps")] == 3
    assert export_lib.metric_name("weird name!") == "tpu_dist_weird_name_"
    assert vals["tpu_dist_weird_name_"] == 1


def test_metric_name_prefix_and_grammar():
    for raw in ("train.steps", "9lives", "a.b-c/d", "mem.bytes_in_use"):
        name = export_lib.metric_name(raw)
        assert name.startswith("tpu_dist_")
        assert re.match(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$", name), name


def test_parse_roundtrip_including_labels():
    text = export_lib.render(
        {"a.b": 1.5, "c": 2},
        {"alert_active": {"r1": 1.0}},
    )
    vals = export_lib.parse(text)
    assert vals[export_lib.metric_name("a.b")] == 1.5
    assert vals['tpu_dist_alert_active{rule="r1"}'] == 1.0


# -- textfile publication ----------------------------------------------------


def test_textfile_write_is_atomic_no_partial_observable(tmp_path):
    """A reader polling the textfile while the writer republishes in a
    tight loop must only ever see complete, EOF-terminated expositions —
    the tmp+rename discipline, observed from the outside."""
    path = str(tmp_path / "m.prom")
    ex = MetricsExporter(textfile=path, min_interval=0.0)
    stop = threading.Event()
    bad = []

    def reader():
        while not stop.is_set():
            try:
                with open(path) as f:
                    text = f.read()
            except FileNotFoundError:
                continue
            if not text.endswith("# EOF\n"):
                bad.append(text[-40:])

    t = threading.Thread(target=reader)
    t.start()
    try:
        for i in range(300):
            ex.update({"train.steps": i, "filler.value": i * 2.5}, force=True)
    finally:
        stop.set()
        t.join()
        ex.close()
    assert not bad, f"torn exposition observed: {bad[:3]}"
    _assert_valid_exposition(open(path).read())


def test_textfile_throttle_matches_heartbeat_grain(tmp_path):
    path = str(tmp_path / "m.prom")
    ex = MetricsExporter(textfile=path, min_interval=60.0)
    assert ex.update({"a": 1}) is True          # first write lands
    assert ex.update({"a": 2}) is False         # throttled
    assert export_lib.parse(open(path).read())["tpu_dist_a"] == 1
    assert ex.update({"a": 3}, force=True) is True  # force bypasses
    assert export_lib.parse(open(path).read())["tpu_dist_a"] == 3
    ex.close()


# -- HTTP endpoint -----------------------------------------------------------


def test_http_endpoint_refused_on_nonzero_rank():
    with pytest.raises(ValueError, match="rank-0-only"):
        MetricsExporter(port=0, rank=3)
    # textfile-only export works on any rank (per-rank derived paths)
    ex = MetricsExporter(rank=3)
    ex.close()


def test_http_endpoint_serves_last_snapshot_under_concurrent_scrapes():
    ex = MetricsExporter(port=0, rank=0)
    try:
        ex.update({"train.steps": 0}, force=True)
        url = f"http://127.0.0.1:{ex.port}/metrics"
        errors = []

        def scraper():
            for _ in range(20):
                try:
                    with urllib.request.urlopen(url, timeout=10) as r:
                        assert r.status == 200
                        ctype = r.headers["Content-Type"]
                        body = r.read().decode()
                    assert "openmetrics-text" in ctype
                    _assert_valid_exposition(body)
                except Exception as e:  # surfaced below with context
                    errors.append(repr(e))
                    return

        threads = [threading.Thread(target=scraper) for _ in range(4)]
        for t in threads:
            t.start()
        # republish concurrently with the scrape storm
        for i in range(50):
            ex.update({"train.steps": i}, force=True)
        for t in threads:
            t.join()
        assert not errors, errors
        # non-/metrics paths 404
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{ex.port}/nope", timeout=10
            )
    finally:
        ex.close()


def test_scrape_helper_reads_textfile_and_http(tmp_path):
    path = str(tmp_path / "m.prom")
    ex = MetricsExporter(textfile=path, port=0, rank=0)
    try:
        ex.update({"train.steps": 7}, force=True)
        for vals in (
            export_lib.scrape(textfile=path),
            export_lib.scrape(port=ex.port),
        ):
            assert vals[export_lib.metric_name("train.steps")] == 7
    finally:
        ex.close()
    assert export_lib.scrape(textfile=str(tmp_path / "absent")) is None
    assert export_lib.scrape() is None


# -- alert rules: spec loading ----------------------------------------------


def test_load_rules_default_library():
    rules = alerts_lib.load_rules("default")
    names = {r.name for r in rules}
    assert {"stall_high", "mfu_low", "goodput_low", "grad_norm_high",
            "heartbeat_stale", "retrace"} <= names


def test_load_rules_toml_with_builtin_override(tmp_path):
    spec = tmp_path / "rules.toml"
    spec.write_text(
        "# comment\n"
        "[[rule]]\n"
        'name = "stall"\n'
        'metric = "data_stall_frac"\n'
        'op = ">"\n'
        "threshold = 0.5\n"
        "sustain = 3\n"
        "cooldown = 2\n"
        "profile = true\n"
        "\n"
        "[[rule]]\n"
        'builtin = "mfu_low"\n'
        "threshold = 0.4\n"
    )
    rules = alerts_lib.load_rules(str(spec))
    assert len(rules) == 2
    stall, mfu = rules
    assert (stall.sustain, stall.cooldown, stall.profile) == (3, 2, True)
    assert mfu.name == "mfu_low" and mfu.threshold == 0.4
    assert mfu.op == "<"  # inherited from the builtin


def test_load_rules_json(tmp_path):
    spec = tmp_path / "rules.json"
    spec.write_text(json.dumps({"rule": [
        {"name": "r1", "metric": "m", "op": "<", "threshold": 1.0},
    ]}))
    (rule,) = alerts_lib.load_rules(str(spec))
    assert rule.name == "r1" and rule.sustain == 1


def test_example_rules_file_parses():
    # the shipped example must stay loadable (it is the docs' grammar)
    rules = alerts_lib.load_rules(
        os.path.join(_REPO_ROOT, "tools", "alert_rules.toml")
    )
    assert {r.name for r in rules} >= {"stall_high", "mfu_low", "retrace"}


@pytest.mark.parametrize("body,err", [
    ('[[rule]]\nname = "x"\nmetric = "m"\nop = "!!"\nthreshold = 1\n', "op"),
    ('[[rule]]\nname = "x"\nmetric = "m"\nop = ">"\nthreshold = 1\nsustain = 0\n',
     "sustain"),
    ('[[rule]]\nname = "x"\nmetric = "m"\nop = ">"\n', "missing"),
    ('[[rule]]\nbuiltin = "nope"\n', "builtin"),
    ('[[rule]]\nname = "x"\nmetric = "m"\nop = ">"\nthreshold = 1\nbogus = 2\n',
     "unknown field"),
    ('[[rule]]\nname = "x"\nmetric = "m"\nop = ">"\nthreshold = 1\n'
     '[[rule]]\nname = "x"\nmetric = "m"\nop = "<"\nthreshold = 2\n',
     "duplicate"),
    ('[[rule]]\nname = "x"\nmetric = "m"\nop = ">"\nthreshold = "0.3"\n',
     "threshold must be a number"),
], ids=["bad-op", "zero-sustain", "missing-fields", "unknown-builtin",
        "unknown-field", "dup-names", "quoted-threshold"])
def test_load_rules_rejects_malformed_specs(tmp_path, body, err):
    spec = tmp_path / "rules.toml"
    spec.write_text(body)
    with pytest.raises(ValueError, match=err):
        alerts_lib.load_rules(str(spec))


def test_load_rules_rejects_unknown_extension_and_empty(tmp_path):
    with pytest.raises(ValueError, match="toml"):
        alerts_lib.load_rules("rules.yaml")
    empty = tmp_path / "empty.toml"
    empty.write_text("# nothing\n")
    with pytest.raises(ValueError, match="non-empty"):
        alerts_lib.load_rules(str(empty))


# -- alert engine: sustain / cooldown / delta --------------------------------


def _engine(**kw):
    defaults = dict(name="r", metric="m", op=">", threshold=10.0)
    defaults.update(kw)
    return alerts_lib.AlertEngine([alerts_lib.AlertRule(**defaults)])


def test_sustain_requires_consecutive_breaches():
    eng = _engine(sustain=3)
    assert eng.observe({"m": 20}) == []
    assert eng.observe({"m": 20}) == []
    assert eng.observe({"m": 5}) == []     # clean window resets the streak
    assert eng.observe({"m": 20}) == []
    assert eng.observe({"m": 20}) == []
    fired = eng.observe({"m": 20})
    assert len(fired) == 1 and fired[0]["sustained"] == 3
    assert fired[0]["rule"] == "r" and fired[0]["op"] == ">"


def test_cooldown_suppresses_refire_then_releases():
    eng = _engine(sustain=1, cooldown=2)
    assert len(eng.observe({"m": 20})) == 1   # fires
    assert eng.observe({"m": 20}) == []       # cooldown 2→1
    assert eng.observe({"m": 20}) == []       # cooldown 1→0
    assert len(eng.observe({"m": 20})) == 1   # refires


def test_absent_metric_leaves_streak_untouched():
    eng = _engine(sustain=2)
    assert eng.observe({"m": 20}) == []
    # a window at another cadence without the metric: neither advance
    # nor reset (the mixed epoch/step feeding contract)
    assert eng.observe({"other": 1}) == []
    fired = eng.observe({"m": 20})
    assert len(fired) == 1


def test_delta_rule_fires_on_change_not_level():
    eng = _engine(metric="compile.retraces", threshold=0.0, delta=True)
    assert eng.observe({"compile.retraces": 5}) == []   # first sighting
    assert eng.observe({"compile.retraces": 5}) == []   # no change
    fired = eng.observe({"compile.retraces": 6})        # +1 this window
    assert len(fired) == 1 and fired[0]["value"] == 1.0
    assert fired[0].get("delta") is True


def test_seed_deltas_baselines_counters_born_mid_run():
    """A counter that does not exist yet (compile.retraces before the
    first retrace) must alert on its FIRST increment once seeded — not
    spend that increment establishing a baseline."""
    eng = _engine(metric="compile.retraces", threshold=0.0, delta=True)
    eng.seed_deltas({"train.steps": 5})        # retraces absent → baseline 0
    fired = eng.observe({"compile.retraces": 1})
    assert len(fired) == 1 and fired[0]["value"] == 1.0
    # seeding with a live value baselines there instead
    eng2 = _engine(metric="compile.retraces", threshold=0.0, delta=True)
    eng2.seed_deltas({"compile.retraces": 4})
    assert eng2.observe({"compile.retraces": 4}) == []
    assert len(eng2.observe({"compile.retraces": 5})) == 1


def test_active_gauge_tracks_sustained_state():
    eng = _engine(sustain=2, cooldown=10)
    eng.observe({"m": 20})
    assert eng.active() == {"r": 0.0}         # breaching, not yet sustained
    eng.observe({"m": 20})
    assert eng.active() == {"r": 1.0}         # fired
    eng.observe({"m": 20})
    assert eng.active() == {"r": 1.0}         # still breaching in cooldown
    eng.observe({"m": 1})
    assert eng.active() == {"r": 0.0}         # clean window clears it


def test_engine_rejects_duplicate_rule_names():
    r = alerts_lib.AlertRule("r", "m", ">", 1.0)
    with pytest.raises(ValueError, match="duplicate"):
        alerts_lib.AlertEngine([r, r])


# -- heartbeat torn-read hardening ------------------------------------------


def test_heartbeat_read_returns_previous_parse_on_torn_file(tmp_path):
    from tpu_dist.obs import heartbeat as hb_lib

    path = str(tmp_path / "hb.json")
    hb = hb_lib.Heartbeat(path)
    hb.beat(epoch=1, step=5, force=True)
    good = hb_lib.read(path)
    assert good["epoch"] == 1 and good["step"] == 5
    before = counters.get("heartbeat.torn_reads")
    # a torn write (atomic-replace race on NFS): truncate mid-JSON
    full = open(path).read()
    with open(path, "w") as f:
        f.write(full[: len(full) // 2])
    torn = hb_lib.read(path)
    assert torn == good                      # previous parse, not None
    assert counters.get("heartbeat.torn_reads") == before + 1
    # a genuinely absent file is still the clean-exit signal
    os.remove(path)
    assert hb_lib.read(path) is None
    # ...and the stale cache must not resurrect after the removal
    with open(path, "w") as f:
        f.write("{not json")
    assert hb_lib.read(path) is None


# -- bench capture fingerprints: stale detection -----------------------------


def _bench_rec(metric, value, cap):
    return {"metric": metric, "value": value, "unit": "images/sec",
            "mfu": 0.5, "capture": cap}


def test_compare_bench_flags_reemitted_capture_as_stale(tmp_path):
    from tpu_dist.obs import compare as compare_lib

    cap = {"host": "h1", "bench_run_id": "abc123", "mono_s": 10.0}
    fresh = {"host": "h1", "bench_run_id": "def456", "mono_s": 99.0}
    base = tmp_path / "base.json"
    cand = tmp_path / "cand.json"
    base.write_text(
        json.dumps(_bench_rec("m1", 100.0, cap)) + "\n"
        + json.dumps(_bench_rec("m2", 50.0, cap)) + "\n"
    )
    # candidate re-emits m1's capture byte-identically; m2 is fresh
    cand.write_text(
        json.dumps(_bench_rec("m1", 100.0, cap)) + "\n"
        + json.dumps(_bench_rec("m2", 52.0, fresh)) + "\n"
    )
    result = compare_lib.compare_files(
        str(base), str(cand), threshold=0.05, bench=True
    )
    stale_rows = [r for r in result["rows"] if r["verdict"] == "STALE"]
    assert len(stale_rows) == 1 and stale_rows[0]["metric"] == "m1"
    assert result["stale"] == 1
    assert result["regressions"] == 0
    # stale rows never count as compared — an all-stale candidate
    # compares nothing and the CLI exits 2 (broken gate, never a pass)
    cand.write_text(
        json.dumps(_bench_rec("m1", 100.0, cap)) + "\n"
        + json.dumps(_bench_rec("m2", 50.0, cap)) + "\n"
    )
    from tpu_dist.obs.__main__ import main as obs_main

    rc = obs_main(["compare", str(base), str(cand), "--bench"])
    assert rc == 2


def test_compare_bench_flags_selfdeclared_stale_fallback(tmp_path):
    """bench's last-good fallback stamps stale:true on the record it
    re-emits (fresh fingerprint or none at all) — the gate must flag it,
    not compare it as a fresh measurement."""
    from tpu_dist.obs import compare as compare_lib

    base = tmp_path / "base.json"
    cand = tmp_path / "cand.json"
    base.write_text(json.dumps(_bench_rec(
        "m1", 100.0, {"host": "h1", "bench_run_id": "aaa111", "mono_s": 1.0}
    )) + "\n")
    cand.write_text(json.dumps({
        **_bench_rec("m1", 100.0,
                     {"host": "h1", "bench_run_id": "bbb222", "mono_s": 2.0}),
        "stale": True,
    }) + "\n")
    result = compare_lib.compare_files(
        str(base), str(cand), threshold=0.05, bench=True
    )
    assert result["stale"] == 1 and result["compared"] == 0
    (row,) = result["rows"]
    assert row["verdict"] == "STALE" and row["candidate"] == "stale capture"


def test_bench_summarize_flags_duplicate_and_selfdeclared_stale(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    cap = {"host": "h1", "bench_run_id": "abc123", "mono_s": 10.0}
    path = tmp_path / "bench.json"
    path.write_text(
        json.dumps(_bench_rec("m1", 100.0, cap)) + "\n"
        + json.dumps(_bench_rec("m1_again", 100.0, cap)) + "\n"  # re-emission
        + json.dumps({"metric": "legacy", "value": 1.0}) + "\n"  # pre-stamp
        + json.dumps({"metric": "fallback", "value": 2.0, "stale": True,
                      "age_days": 30,
                      "capture": {"host": "h1", "bench_run_id": "zzz",
                                  "mono_s": 1.0}}) + "\n"
    )
    assert obs_main(["summarize", str(path), "--bench"]) == 0
    out = capsys.readouterr().out
    assert "2 STALE" in out
    assert "re-emits m1" in out
    assert "1 without capture fingerprint" in out
    assert "30d old" in out


def test_bench_stamps_capture_fingerprint():
    import bench

    rec = bench._stamped({"metric": "x", "value": 1.0})
    cap = rec["capture"]
    assert cap["host"] == socket.gethostname()
    assert re.match(r"^[0-9a-f]{12}$", cap["bench_run_id"])
    assert isinstance(cap["mono_s"], float)
    # two records from one process share the invocation id but carry
    # distinct capture instants — only a byte-identical COPY matches
    rec2 = bench._stamped({"metric": "y", "value": 2.0})
    assert rec2["capture"]["bench_run_id"] == cap["bench_run_id"]


# -- obs tail ----------------------------------------------------------------


def test_log_follower_consumes_only_complete_lines(tmp_path):
    from tpu_dist.obs.tail import LogFollower

    path = str(tmp_path / "run.jsonl")
    f = open(path, "w")
    fol = LogFollower(path)
    assert fol.poll() == []
    f.write('{"kind": "train_epoch", "epoch": 0}\n{"kind": "ev')
    f.flush()
    recs = fol.poll()
    assert [r["kind"] for r in recs] == ["train_epoch"]  # torn tail held
    f.write('al", "epoch": 0}\n')
    f.flush()
    recs = fol.poll()
    assert [r["kind"] for r in recs] == ["eval"]         # completed now
    # garbage line: counted, not fatal (the summarize tolerance)
    f.write("not json\n")
    f.flush()
    assert fol.poll() == []
    assert fol.bad_lines == 1
    f.close()


def test_log_follower_resets_on_truncation(tmp_path):
    from tpu_dist.obs.tail import LogFollower

    path = str(tmp_path / "run.jsonl")
    with open(path, "w") as f:
        f.write('{"kind": "train_epoch", "epoch": 0}\n')
    fol = LogFollower(path)
    assert len(fol.poll()) == 1
    with open(path, "w") as f:  # rotated: a fresh run reused the path
        f.write('{"kind": "eval", "epoch": 7}\n')
    recs = fol.poll()
    # detection is size-based (a shrunken file resets the cursor); the
    # rotated content is re-read from the start
    assert len(recs) == 1 and recs[0]["epoch"] == 7


_GOLDEN_RECORDS = [
    {"kind": "train_epoch", "epoch": 0, "run_id": "r1", "schema_version": 5,
     "images_per_sec": 1234.5, "step_time_p50": 0.012,
     "data_stall_frac": 0.05, "mfu": 0.41, "loss": 2.31},
    {"kind": "goodput", "epoch": 0, "run_id": "r1",
     "window_s": 10.0, "productive_s": 8.0},
    {"kind": "eval", "epoch": 0, "run_id": "r1", "top1": 12.5},
    {"kind": "train_epoch", "epoch": 1, "run_id": "r1", "schema_version": 5,
     "images_per_sec": 1500.0, "step_time_p50": 0.010,
     "data_stall_frac": 0.35, "mfu": 0.45, "loss": 2.10},
    {"kind": "alert", "epoch": 1, "run_id": "r1", "rule": "stall_high",
     "metric": "data_stall_frac", "value": 0.35, "op": ">",
     "threshold": 0.3, "sustained": 2},
    {"kind": "straggler", "epoch": 1, "run_id": "r1", "worst_rank": 3,
     "skew": 1.8},
    {"kind": "anomaly", "epoch": 1, "step": 4, "run_id": "r1",
     "anomaly": "loss_spike", "value": 9.9},
]

_GOLDEN_EXPECTED = (
    "run r1 — 7 record(s), 2 epoch(s), 1 alert(s) fired",
    "epoch     img/s   p50_ms  stall%    mfu  goodput      loss  val_top1",
    "    0    1234.5     12.0     5.0  0.410    80.0%    2.3100     12.50",
    "    1    1500.0     10.0    35.0  0.450        -    2.1000         -",
    "  ALERT stall_high: data_stall_frac 0.35 > 0.3 (sustained 2 "
    "window(s), epoch 1)",
    "  straggler: process 3 at 1.8x median (epoch 1)",
    "  anomaly loss_spike at epoch 1 step 4: value 9.9",
    "heartbeat: #9 epoch 1 step 4 phase 'train', age 2.5s",
)


def test_tail_golden_render_from_recorded_jsonl(tmp_path):
    """The dashboard frame is a stable, deterministic rendering of a
    recorded log (fixed clock injected) — the golden the docs quote."""
    from tpu_dist.obs.tail import LogFollower, TailState

    path = str(tmp_path / "run.jsonl")
    with open(path, "w") as f:
        for rec in _GOLDEN_RECORDS:
            f.write(json.dumps(rec) + "\n")
    state = TailState()
    state.add(LogFollower(path).poll())
    hb = {"counter": 9, "epoch": 1, "step": 4, "phase": "train", "ts": 100.0}
    out = state.render(hb, now_wall=102.5)
    assert out == "\n".join(_GOLDEN_EXPECTED), out


def test_tail_marks_stale_heartbeat_and_resume_segments():
    from tpu_dist.obs.tail import TailState

    state = TailState()
    state.add([
        {"kind": "train_epoch", "epoch": 0, "run_id": "a", "loss": 1.0},
        {"kind": "train_epoch", "epoch": 1, "run_id": "b", "loss": 0.9},
    ])
    out = state.render(
        {"counter": 1, "epoch": 1, "step": 0, "phase": "train", "ts": 0.0},
        now_wall=120.0,
    )
    assert "STALE" in out                      # 120s-old beat
    assert "resumed: new segment b" in out


def test_tail_cli_once_renders_and_exits(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    path = tmp_path / "run.jsonl"
    with open(path, "w") as f:
        for rec in _GOLDEN_RECORDS:
            f.write(json.dumps(rec) + "\n")
    assert obs_main(["tail", str(path), "--once"]) == 0
    out = capsys.readouterr().out
    assert "run r1" in out and "ALERT stall_high" in out
    # an empty/absent log is exit 1, like the other subcommands
    assert obs_main(["tail", str(tmp_path / "absent.jsonl"), "--once"]) == 1


def test_tail_follow_exits_on_final_record(tmp_path):
    """Follow mode: a concurrent writer appends epochs then the run-end
    totals record; the loop must pick them up incrementally and exit."""
    from tpu_dist.obs.tail import run_tail

    path = str(tmp_path / "run.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(_GOLDEN_RECORDS[0]) + "\n")

    def writer():
        time.sleep(0.3)
        with open(path, "a") as f:
            f.write(json.dumps(_GOLDEN_RECORDS[3]) + "\n")
            f.flush()
            time.sleep(0.3)
            f.write(json.dumps({
                "kind": "goodput", "final": True, "run_id": "r1",
                "goodput_frac": 0.7, "elapsed_s": 12.0,
            }) + "\n")

    t = threading.Thread(target=writer)
    t.start()
    buf = io.StringIO()
    rc = run_tail(path, interval=0.1, stream=buf)
    t.join()
    assert rc == 0
    out = buf.getvalue()
    assert "run ended: goodput 70.0%" in out
    assert "1500.0" in out                     # the appended epoch arrived


# -- summarize: alert records ------------------------------------------------


def test_summarize_folds_alert_records():
    from tpu_dist.obs.summarize import format_text, summarize

    report = summarize(_GOLDEN_RECORDS)
    assert report["alerts"] == [{
        "epoch": 1, "rule": "stall_high", "metric": "data_stall_frac",
        "value": 0.35, "threshold": 0.3, "op": ">", "sustained": 2,
    }]
    text = format_text(report)
    assert "alert: stall_high fired at epoch 1" in text
    assert "sustained 2 window(s)" in text


# -- TD109 -------------------------------------------------------------------


def test_td109_live_export_noop_gate():
    from tpu_dist.analysis.jaxpr_audit import live_export_noop_violations

    assert live_export_noop_violations() == []


def test_td109_rule_registered():
    from tpu_dist.analysis.rules import RULES

    assert "TD109" in RULES


# -- e2e acceptance ----------------------------------------------------------


@pytest.mark.slow  # full trainer fit (~20 s incl. compiles): excluded from
# the timed tier-1 gate; gates in the CI export step, which runs this
# module without the slow filter
def test_e2e_live_run_scrape_matches_jsonl_and_stall_rule_fires(tmp_path):
    """Acceptance: during a live run, scraping rank 0's /metrics (and
    reading --metrics_file) returns OpenMetrics-parseable output whose
    counter values match the JSONL for the same epoch window, and a
    threshold rule on stall_frac demonstrably fires an ``alert`` record
    + ``alert_active`` exporter gauge in-run."""
    from tests.helpers import tiny_resnet
    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer, register_model

    register_model(
        "tiny_live_e2e", lambda num_classes=10: tiny_resnet(num_classes)
    )
    log = str(tmp_path / "run.jsonl")
    mf = str(tmp_path / "metrics.prom")
    rules = tmp_path / "rules.toml"
    # any measured stall sustains this rule from epoch 0 — the point is
    # to watch the full fire path (record + gauge) on a real run
    rules.write_text(
        "[[rule]]\n"
        'name = "stall_watch"\n'
        'metric = "data_stall_frac"\n'
        'op = ">="\n'
        "threshold = 0.0\n"
        "sustain = 1\n"
        "cooldown = 0\n"
    )
    with socket.socket() as s:  # cfg takes a real port (0 means off)
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_live_e2e", num_classes=10,
        batch_size=64, epochs=2, steps_per_epoch=3, eval_every=0,
        synthetic_n=640, log_every=2, log_file=log, seed=0,
        metrics_file=mf, metrics_port=port, alert_rules=str(rules),
        heartbeat_file=str(tmp_path / "hb.json"),
    )
    trainer = Trainer(cfg)

    scrapes = []
    stop = threading.Event()

    def scraper():
        # live mid-run scrapes of BOTH surfaces, concurrent with training
        while not stop.is_set():
            port = trainer._exporter.port if trainer._exporter else None
            if port:
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=5
                    ) as r:
                        scrapes.append(r.read().decode())
                except OSError:
                    pass
            time.sleep(0.1)

    t = threading.Thread(target=scraper)
    t.start()
    try:
        trainer.fit()
    finally:
        stop.set()
        t.join()
    assert scrapes, "no live scrape landed during the run"
    for text in scrapes:
        _assert_valid_exposition(text)
    # the textfile's final exposition survives the run (left behind by
    # design) and its counters match the JSONL's last snapshot exactly
    final = export_lib.parse(open(mf).read())
    records = [json.loads(line) for line in open(log)]
    last_counters = [
        r["counters"] for r in records if isinstance(r.get("counters"), dict)
    ][-1]
    for name in ("train.steps", "train.epochs", "heartbeat.beats",
                 "loader.batches_consumed", "alerts.fired"):
        assert final[export_lib.metric_name(name)] == pytest.approx(
            last_counters[name]
        ), name
    # per-epoch-window match: a mid-run scrape taken at the epoch-1
    # boundary carries epoch 0's closed rollup — its train.steps gauge
    # must equal the JSONL train_epoch record's counter for that window
    epoch_recs = [r for r in records if r.get("kind") == "train_epoch"]
    assert len(epoch_recs) == 2
    mid = [
        export_lib.parse(s) for s in scrapes
        if export_lib.parse(s).get(export_lib.metric_name("train.epoch")) == 0
    ]
    if mid:  # timing-dependent which scrapes landed inside epoch 0's window
        assert mid[-1][export_lib.metric_name("train.steps")] <= (
            epoch_recs[0]["counters"]["train.steps"]
        )
    # the stall rule fired in-run: alert record in the JSONL...
    alerts = [r for r in records if r.get("kind") == "alert"]
    assert alerts and alerts[0]["rule"] == "stall_watch"
    assert alerts[0]["metric"] == "data_stall_frac"
    assert records[0]["schema_version"] == 15  # v15: causal decision tracing (ISSUE 19)
    # ...and the exporter gauge flipped (active through the final window:
    # cooldown 0 + every epoch breaches, so the last exposition holds 1)
    assert final['tpu_dist_alert_active{rule="stall_watch"}'] == 1.0
    # the dashboard renders the finished run (CLI smoke over real data)
    from tpu_dist.obs.tail import LogFollower, TailState

    state = TailState()
    state.add(LogFollower(log).poll())
    frame = state.render(None)
    assert "ALERT stall_watch" in frame and "run ended" in frame


@pytest.mark.slow  # two coordinated trainer processes (~1 min): excluded
# from the timed tier-1 gate; gates in the CI export step. Skips where the
# jaxlib CPU backend lacks cross-process collectives (the test_multihost
# contract).
def test_e2e_two_process_run_rank0_endpoint_and_per_rank_textfiles(tmp_path):
    """A REAL 2-process CPU run under the launcher: rank 0 binds the
    /metrics endpoint and is scraped live from outside, rank 1 serves no
    endpoint but writes its derived .h1 textfile — and the watchdog
    plumbing (--metrics_dir) injects the paths."""
    port = None
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO_ROOT
    env.pop("XLA_FLAGS", None)
    mdir = tmp_path / "metrics"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "tpu_dist.cli.launch",
            "--nproc", "2", "--devices_per_proc", "1",
            "--metrics_dir", str(mdir), "--",
            sys.executable, "-m", "tpu_dist.cli.train",
            "--dataset", "synthetic", "--model", "resnet18",
            "--num_classes", "100", "--synthetic_n", "256",
            "--batch_size", "32", "--epochs", "2", "--steps_per_epoch", "2",
            "--eval_every", "0", "--seed", "0", "--log_every", "1",
            "--metrics_port", str(port),
            "--log_file", str(tmp_path / "run.jsonl"),
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=_REPO_ROOT,
    )
    scrapes = []
    try:
        deadline = time.monotonic() + 240
        while proc.poll() is None and time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=2
                ) as r:
                    scrapes.append(r.read().decode())
            except OSError:
                pass
            time.sleep(0.25)
        out, _ = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    if "Multiprocess computations aren't implemented on the CPU backend" in out:
        pytest.skip("CPU backend lacks multiprocess collectives in this jaxlib")
    assert proc.returncode == 0, out
    for text in scrapes:
        _assert_valid_exposition(text)
    # per-rank textfiles: rank 0 bare, rank 1 derived .h1 — and rank 1
    # never bound a port (a second bind on the same port would have
    # crashed the run; the rank-0-only refusal is also unit-tested)
    base = str(mdir / "metrics.prom")
    v0 = export_lib.scrape(textfile=base)
    v1 = export_lib.scrape(textfile=base + ".h1")
    assert v0 and v1
    assert v0[export_lib.metric_name("train.steps")] == 4
    assert v1[export_lib.metric_name("train.steps")] == 4
