"""Fleet-level observability (ISSUE 6): the goodput ledger's
sum-equals-wall-clock invariant, preemption/restart loss attribution,
triggered on-device profiling (+ the TD108 noop gate), pod-wide
aggregation, the compare --goodput gate, forward-compat record skipping,
and the launcher heartbeat watchdog."""

import json
import os
import signal
import sys
import time

import pytest

from tpu_dist.obs import counters, goodput, spans
from tpu_dist.obs import profile as profile_lib
from tpu_dist.obs.summarize import format_text, load_records, summarize


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Spans/counters are process-global; isolate every test."""
    spans.disable()
    spans.drain()
    counters.reset()
    yield
    spans.disable()
    spans.drain()
    counters.reset()


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return str(path)


# -- GoodputLedger units -----------------------------------------------------


def test_ledger_windows_partition_wallclock_exactly():
    led = goodput.GoodputLedger(t0=100.0)
    led.add("productive", 6.0)
    led.add("data_stall", 1.0)
    led.add("ckpt", 0.5)
    rec = led.window_record(now=110.0)
    assert rec["window_s"] == 10.0
    assert rec["productive_s"] == 6.0 and rec["data_stall_s"] == 1.0
    # the remainder is derived, never hidden
    assert rec["unattributed_s"] == pytest.approx(2.5)
    assert sum(
        rec[f"{b}_s"] for b in goodput.ALL_BUCKETS
    ) == pytest.approx(rec["window_s"])
    # second window chains from the first's close
    led.add("eval", 2.0)
    rec2 = led.window_record(now=114.0)
    assert rec2["window_s"] == 4.0 and rec2["unattributed_s"] == 2.0
    totals = led.run_totals(now=114.0)
    assert totals["elapsed_s"] == 14.0
    assert totals["productive_s"] == 6.0 and totals["eval_s"] == 2.0
    assert totals["goodput_frac"] == pytest.approx(6.0 / 14.0, abs=1e-4)
    line = goodput.ledger_line(totals)
    assert "42.9%" in line and "14.0s" in line


def test_ledger_rejects_unknown_bucket_and_clamps_negative():
    led = goodput.GoodputLedger(t0=0.0)
    with pytest.raises(ValueError):
        led.add("coffee", 1.0)
    led.add("productive", -5.0)  # clock weirdness must not corrupt books
    assert led.window_value("productive") == 0.0
    # over-attribution clamps the remainder at zero, not negative
    led.add("productive", 50.0)
    rec = led.window_record(now=10.0)
    assert rec["unattributed_s"] == 0.0


def test_ledger_timed_is_exception_safe():
    led = goodput.GoodputLedger(t0=0.0)
    with pytest.raises(RuntimeError):
        with led.timed("ckpt"):
            time.sleep(0.01)
            raise RuntimeError("disk on fire")
    assert led.window_value("ckpt") >= 0.01


# -- offline run_ledger: segments and restart gaps ---------------------------


def _goodput_rec(run_id, ts, rel_s, **fields):
    return {"kind": "goodput", "run_id": run_id, "ts": ts, "rel_s": rel_s,
            "schema_version": 4, **fields}


def test_run_ledger_folds_segments_and_charges_restart_gap():
    records = [
        _goodput_rec("a-1", 1000.0, 10.0, epoch=0, window_s=10.0,
                     productive_s=8.0, compile_s=1.0, unattributed_s=1.0),
        _goodput_rec("a-1", 1002.0, 12.0, final=True, elapsed_s=12.0,
                     productive_s=8.0, compile_s=1.0, ckpt_s=0.5,
                     preempt_s=1.0, unattributed_s=1.5, goodput_frac=0.667),
        # resumed segment: constructed at wall 1010 (ts - rel_s), so the
        # run lost 1010 - 1002 = 8s to the restart
        _goodput_rec("b-2", 1011.0, 1.0, epoch=1, window_s=1.0,
                     productive_s=0.5, unattributed_s=0.5),
        _goodput_rec("b-2", 1015.0, 5.0, final=True, elapsed_s=5.0,
                     productive_s=4.0, unattributed_s=1.0, goodput_frac=0.8),
    ]
    led = goodput.run_ledger(records)
    assert led["n_segments"] == 2
    assert led["restart_gap_s"] == pytest.approx(8.0)
    assert led["preempt_s"] == pytest.approx(1.0 + 8.0)  # in-process + gap
    assert led["elapsed_s"] == pytest.approx(12.0 + 5.0 + 8.0)
    assert led["productive_s"] == pytest.approx(12.0)
    assert led["goodput_frac"] == pytest.approx(12.0 / 25.0, abs=1e-3)


def test_run_ledger_reconstructs_segment_killed_before_final():
    # a crash between the last window record and the final totals: the
    # windows are the books
    records = [
        _goodput_rec("a-1", 1000.0, 10.0, epoch=0, window_s=10.0,
                     productive_s=7.0, unattributed_s=3.0),
        _goodput_rec("a-1", 1005.0, 15.0, epoch=1, window_s=5.0,
                     productive_s=4.0, unattributed_s=1.0),
    ]
    led = goodput.run_ledger(records)
    assert led["elapsed_s"] == pytest.approx(15.0)
    assert led["productive_s"] == pytest.approx(11.0)
    assert goodput.run_ledger([{"kind": "train_epoch", "epoch": 0}]) is None


# -- triggered profiler state machine (fake capture backend) -----------------


@pytest.fixture
def fake_profiler(monkeypatch):
    calls = {"start": [], "stop": 0}
    import jax

    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: calls["start"].append(d)
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace",
        lambda: calls.__setitem__("stop", calls["stop"] + 1),
    )
    return calls


def test_profiler_arm_window_cooldown_and_cap(tmp_path, fake_profiler):
    prof = profile_lib.TriggeredProfiler(
        str(tmp_path), window_steps=2, cooldown_steps=5, max_captures=2
    )
    assert prof.on_step(0) is None          # nothing armed: free
    assert prof.arm("anomaly_loss_spike")
    ev = prof.on_step(1)
    assert ev["event"] == "start" and ev["reason"] == "anomaly_loss_spike"
    assert prof.on_step(2) is None          # window open, 1 of 2 steps
    ev = prof.on_step(3)
    assert ev["event"] == "stop" and ev["steps"] == 2
    assert fake_profiler["stop"] == 1
    # cooldown: an arm inside it stays pending until the cooldown expires
    assert prof.arm("retrace")
    assert prof.on_step(4) is None
    assert prof.on_step(7) is None and prof.armed == "retrace"
    ev = prof.on_step(8)                    # 8 - 3 reaches the cooldown 5
    assert ev is not None and ev["event"] == "start"
    prof.close()
    # cap: both captures spent — further arms are refused and counted
    assert not prof.arm("anomaly_again")
    assert counters.get("profile.skipped_capped") == 1
    assert counters.get("profile.captures") == 2
    assert len(fake_profiler["start"]) == 2


def test_profiler_manual_range_fires_once(tmp_path, fake_profiler):
    prof = profile_lib.TriggeredProfiler(
        str(tmp_path), window_steps=8, manual_range=(3, 5), max_captures=0
    )
    assert prof.on_step(0) is None
    ev = prof.on_step(3)
    assert ev["event"] == "start" and ev["reason"] == "manual"
    assert prof.on_step(4) is None
    ev = prof.on_step(5)                    # [3, 5): stops at b
    assert ev["event"] == "stop" and ev["steps"] == 2
    for s in range(6, 12):                  # manual fires ONCE
        assert prof.on_step(s) is None


def test_profiler_manual_range_longer_than_window_runs_full(
    tmp_path, fake_profiler
):
    """--profile_steps a:b owns its FULL range: window_steps bounds
    triggered captures only (a 50-step manual request must not be
    silently truncated to the 8-step default window)."""
    prof = profile_lib.TriggeredProfiler(
        str(tmp_path), window_steps=3, manual_range=(2, 9), max_captures=0
    )
    ev = prof.on_step(2)
    assert ev["event"] == "start" and ev["window_steps"] == 7
    for s in range(3, 9):                   # steps 3..8 all inside [2, 9)
        assert prof.on_step(s) is None
    ev = prof.on_step(9)
    assert ev["event"] == "stop" and ev["steps"] == 7
    assert fake_profiler["stop"] == 1


def test_profiler_close_reports_actual_steps(tmp_path, fake_profiler):
    """close() mid-window (fit exit, error exits) must report the steps
    that actually ran, flagged aborted — not the planned window."""
    prof = profile_lib.TriggeredProfiler(
        str(tmp_path), window_steps=8, cooldown_steps=0, max_captures=2
    )
    prof.arm("anomaly")
    prof.on_step(5)
    prof.on_step(6)
    prof.on_step(7)                         # 3 of the planned 8 ran
    ev = prof.close()
    assert ev["event"] == "stop" and ev["aborted"]
    assert ev["steps"] == 3
    assert fake_profiler["stop"] == 1


def test_profiler_capture_failure_disables_not_raises(tmp_path, monkeypatch):
    import jax

    def boom(d):
        raise RuntimeError("profiler backend unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    prof = profile_lib.TriggeredProfiler(str(tmp_path), max_captures=3)
    prof.arm("anomaly_x")
    ev = prof.on_step(0)
    assert ev["event"] == "error"
    assert not prof.arm("anomaly_y")        # broken: stands down for good
    assert counters.get("profile.errors") == 1


def test_profile_spec_parsing():
    assert profile_lib.parse_trigger("off") == frozenset()
    assert profile_lib.parse_trigger("auto") == frozenset(
        profile_lib.TRIGGER_KINDS
    )
    assert profile_lib.parse_trigger("anomaly,retrace") == {
        "anomaly", "retrace"
    }
    with pytest.raises(ValueError):
        profile_lib.parse_trigger("anomaly,typo")
    assert profile_lib.parse_steps(None) is None
    assert profile_lib.parse_steps("3:7") == (3, 7)
    for bad in ("7:3", "3", "a:b", "-1:2", "3:3"):
        with pytest.raises(ValueError):
            profile_lib.parse_steps(bad)


def test_trainer_rejects_bad_profile_configs(tmp_path):
    from tests.helpers import tiny_resnet
    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer, register_model

    register_model("tiny_gp_cfg", lambda num_classes=10: tiny_resnet(num_classes))
    base = dict(
        dataset="synthetic", model="tiny_gp_cfg", num_classes=10,
        batch_size=64, epochs=1, synthetic_n=64, seed=0,
    )
    with pytest.raises(ValueError, match="profile_dir"):
        Trainer(TrainConfig(**base, profile_trigger="auto"))
    with pytest.raises(ValueError, match="a:b"):
        Trainer(TrainConfig(
            **base, profile_steps="oops",
            profile_dir=str(tmp_path / "p"),
        ))
    with pytest.raises(ValueError, match="fused_epoch"):
        Trainer(TrainConfig(
            **base, profile_steps="1:3", fused_epoch=True,
            profile_dir=str(tmp_path / "p"),
        ))


def test_seed_global_step_reanchors_profile_grid():
    """The --profile_steps grid is RUN-global: a resumed process anchors
    it at the restored position (epoch x steps-per-epoch + mid-epoch
    step), so windows already captured before a preemption never
    re-fire at the wrong steps."""
    import types

    from tpu_dist.train.trainer import Trainer

    stub = types.SimpleNamespace(
        train_loader=[None] * 10,
        cfg=types.SimpleNamespace(steps_per_epoch=None),
        start_epoch=3, _resume_step=4,
    )
    Trainer._seed_global_step(stub)
    assert stub._global_step == 3 * 10 + 4
    # --steps_per_epoch caps the per-epoch count, same as train_epoch
    stub.cfg.steps_per_epoch = 6
    Trainer._seed_global_step(stub)
    assert stub._global_step == 3 * 6 + 4


# -- TD108 -------------------------------------------------------------------


@pytest.mark.slow  # ~20 s: opens a REAL jax.profiler capture window
# (the capture-OPEN trace comparison); excluded from the timed tier-1
# gate, runs in the CI goodput step (no slow filter) — ISSUE 7 budget
def test_td108_profile_trigger_noop_gate():
    from tpu_dist.analysis.jaxpr_audit import profile_trigger_noop_violations

    assert profile_trigger_noop_violations() == []


def test_td108_rule_registered():
    from tpu_dist.analysis.rules import RULES

    assert "TD108" in RULES


# -- forward-compat: unknown kinds / future schema ---------------------------


# kinds this reader has never heard of, and the two it once folded: a
# history written before PR 31 holds ``plan`` / ``tune`` lines (the
# planner's and the tuner's announcements, at the current schema) and
# must load and summarise as before, those lines counted as unknown
_FOREIGN_KINDS = {
    "future": (
        [
            {"kind": "hologram", "epoch": 0, "schema_version": 16, "ts": 3.0},
            {"kind": "hologram", "epoch": 1, "schema_version": 16, "ts": 4.0},
            {"kind": "quantum_foam", "schema_version": 16, "ts": 5.0},
        ],
        {"hologram": 2, "quantum_foam": 1}, 3,
    ),
    "retired_plan_tune": (
        [
            {"kind": "plan", "epoch": 0, "schema_version": 15, "ts": 3.0,
             "run_id": "r", "family": "zero1_sgd", "mode": "apply",
             "applied": True, "predicted_step_s": 0.012,
             "gauge_source": "uncalibrated_defaults", "n_candidates": 9,
             "n_refused": 0},
            {"kind": "plan", "epoch": 0, "schema_version": 15, "ts": 4.0,
             "run_id": "r", "family": "zero1_sgd", "mode": "apply",
             "predicted_step_s": 0.012, "achieved_step_s": 0.02,
             "prediction_source": "plan"},
            {"kind": "tune", "epoch": 0, "schema_version": 15, "ts": 5.0,
             "run_id": "r", "family": "zero1_sgd", "report": "t.json",
             "objective": "sched_distance", "applied": {},
             "user_overrides": {}},
        ],
        {"plan": 2, "tune": 1}, 0,
    ),
}


@pytest.mark.parametrize("case", sorted(_FOREIGN_KINDS))
def test_summarize_skips_unknown_kinds_with_count(case, tmp_path):
    """The mixed v4/v5(/v6) regression: older tooling reading a newer log
    (and vice versa) must skip-with-count, not crash or silently drop."""
    foreign, skipped, newer = _FOREIGN_KINDS[case]
    path = tmp_path / "run.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in [
        {"kind": "train_epoch", "epoch": 0, "run_id": "r", "ts": 1.0,
         "rel_s": 1.0, "schema_version": 3, "epoch_time": 1.0,
         "images_per_sec": 100.0, "loss": 2.0},
        _goodput_rec("r", 2.0, 2.0, epoch=0, window_s=2.0,
                     productive_s=1.5, unattributed_s=0.5),
        *foreign,
    ]))
    records, bad = load_records(str(path))
    assert bad == 0 and len(records) == 2 + len(foreign)
    report = summarize(records)
    assert report["skipped_kinds"] == skipped
    assert report["newer_schema_records"] == newer
    assert report["totals"]["n_epochs"] == 1  # known kinds still parsed
    assert report["goodput"]["productive_s"] == pytest.approx(1.5)
    text = format_text(report)
    n = sum(skipped.values())
    assert f"skipped {n} record(s) of unknown kind(s)" in text
    if newer:
        assert "hologram×2" in text and "newer than this reader" in text
    else:
        assert "plan×2" in text and "newer than this reader" not in text
    # and the compare gate reads such a log against itself as clean
    from tpu_dist.obs import compare

    result = compare.compare_files(str(path), str(path))
    assert result["regressions"] == 0


def test_summarize_renders_goodput_table():
    records = [
        _goodput_rec("r", 1.0, 1.0, epoch=0, window_s=4.0, productive_s=3.0,
                     compile_s=0.5, data_stall_s=0.25, unattributed_s=0.25),
        # run-end teardown window: same epoch number as the row above, but
        # tail-marked so the table can tell them apart
        _goodput_rec("r", 1.5, 1.5, epoch=0, tail=True, window_s=0.5,
                     ckpt_s=0.4, unattributed_s=0.1),
        _goodput_rec("r", 2.0, 2.0, final=True, elapsed_s=4.5,
                     productive_s=3.0, compile_s=0.5, data_stall_s=0.25,
                     ckpt_s=0.4, unattributed_s=0.35, goodput_frac=0.667),
    ]
    report = summarize(records)
    assert len(report["goodput_epochs"]) == 2
    assert report["goodput_epochs"][0].get("tail") is None
    assert report["goodput_epochs"][1]["tail"] is True
    assert report["goodput"]["goodput_frac"] == pytest.approx(3.0 / 4.5, abs=1e-3)
    text = format_text(report)
    assert "goodput (seconds per window):" in text
    assert "   0*" in text                   # the tail row is marked...
    assert "run-end tail window" in text     # ...and the marker explained
    assert "66.7% of 4.5s wall-clock productive" in text


# -- compare --goodput -------------------------------------------------------


def _history_with_goodput(path, frac, stall=0.05):
    productive = round(10.0 * frac, 4)
    return _write_jsonl(path, [
        {"kind": "train_epoch", "epoch": 0, "run_id": "r", "ts": 1.0,
         "rel_s": 1.0, "epoch_time": 10.0, "images_per_sec": 1000.0,
         "loss": 2.0, "data_stall_frac": stall, "step_time_p50": 0.01,
         "step_time_p95": 0.02, "step_time_p99": 0.03},
        _goodput_rec("r", 11.0, 11.0, final=True, elapsed_s=10.0,
                     productive_s=productive, unattributed_s=10.0 - productive,
                     goodput_frac=frac),
    ])


def test_compare_goodput_gate_exit_contract(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    base = _history_with_goodput(tmp_path / "base.jsonl", 0.85)
    worse = _history_with_goodput(tmp_path / "cand.jsonl", 0.60)
    # injected goodput regression → exit 1 (the CI gate contract)
    assert obs_main(["compare", base, worse, "--goodput"]) == 1
    out = capsys.readouterr().out
    assert "goodput_frac" in out and "REGRESSED" in out
    # self-compare is clean, and the gate compares ONLY goodput metrics
    assert obs_main(["compare", base, base, "--goodput", "--format", "json"]) == 0
    result = json.loads(capsys.readouterr().out)
    assert {r["metric"] for r in result["rows"]} == {
        "goodput_frac", "data_stall_frac", "preempt_for_serve_s"
    }
    # full-metric compare also sees the fraction (additive, skipped when
    # a pre-v4 log lacks it)
    assert obs_main(["compare", base, worse]) == 1
    # two goodput-less pre-v4 logs under --goodput: nothing compared on the
    # headline metric → the stall row still anchors the gate; drop it too
    # and the CLI refuses to pass silently
    a = _write_jsonl(tmp_path / "old_a.jsonl",
                     [{"kind": "train_epoch", "epoch": 0, "epoch_time": 1.0,
                       "images_per_sec": 10.0}])
    capsys.readouterr()
    assert obs_main(["compare", a, a, "--goodput"]) == 2


# -- pod aggregation ---------------------------------------------------------


def _host_log(path, name_seed, *, epoch_time, stall, frac, t0=1000.0):
    recs = [
        {"kind": "train_epoch", "epoch": 0, "run_id": f"r-{name_seed}",
         "ts": t0 + epoch_time, "rel_s": epoch_time,
         "epoch_time": epoch_time, "images_per_sec": 5000.0 / epoch_time,
         "loss": 2.0, "data_stall_frac": stall},
        {"kind": "spans", "run_id": f"r-{name_seed}", "ts": t0 + epoch_time,
         "rel_s": epoch_time,
         "events": [{"name": "train/dispatch", "ph": "X", "ts": 1e5,
                     "dur": 5e4, "pid": 0, "tid": 1}]},
        _goodput_rec(f"r-{name_seed}", t0 + epoch_time + 0.5,
                     epoch_time + 0.5, final=True,
                     elapsed_s=epoch_time + 0.5,
                     productive_s=round(frac * (epoch_time + 0.5), 3),
                     unattributed_s=round(
                         (1 - frac) * (epoch_time + 0.5), 3),
                     goodput_frac=frac),
    ]
    return _write_jsonl(path, recs)


def test_pod_report_side_by_side_and_straggler_attribution(tmp_path):
    from tpu_dist.obs import aggregate

    # host1 is the straggler AND stalls on input — attribution: data_stall
    h0 = _host_log(tmp_path / "h0.jsonl", 0, epoch_time=10.0, stall=0.02,
                   frac=0.9)
    h1 = _host_log(tmp_path / "h1.jsonl", 1, epoch_time=25.0, stall=0.6,
                   frac=0.4, t0=1000.2)
    hosts = [(p, load_records(p)[0]) for p in (h0, h1)]
    report = aggregate.pod_report(hosts)
    assert report["n_hosts"] == 2
    assert report["pod"]["worst_goodput_host"] == h1
    assert report["pod"]["goodput_frac_min"] == pytest.approx(0.4)
    (skew,) = report["epoch_skew"]
    assert skew["worst_host"] == h1 and skew["skew"] > 1.4
    assert skew["attribution"] == "data_stall"
    text = aggregate.format_text(report)
    assert "per-host goodput ledgers:" in text
    assert "attribution: data_stall" in text


def test_pod_trace_one_track_per_host_aligned_on_wall_clock(tmp_path):
    from tpu_dist.obs import aggregate

    h0 = _host_log(tmp_path / "h0.jsonl", 0, epoch_time=10.0, stall=0.0,
                   frac=0.9, t0=1000.0)
    # host 1's clock zero sits 2s later on the wall — its track must shift
    h1 = _host_log(tmp_path / "h1.jsonl", 1, epoch_time=10.0, stall=0.0,
                   frac=0.9, t0=1002.0)
    hosts = [(p, load_records(p)[0]) for p in (h0, h1)]
    trace = aggregate.pod_trace(hosts)
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert pids == {0, 1}
    names = {
        e["args"]["name"] for e in trace["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert names == {h0, h1}
    span0 = next(e for e in trace["traceEvents"]
                 if e["pid"] == 0 and e["name"] == "train/dispatch")
    span1 = next(e for e in trace["traceEvents"]
                 if e["pid"] == 1 and e["name"] == "train/dispatch")
    assert span1["ts"] - span0["ts"] == pytest.approx(2e6, rel=1e-3)
    for e in trace["traceEvents"]:  # structurally Perfetto-loadable
        assert isinstance(e.get("name"), str) and "ph" in e


def test_pod_cli_merges_logs_and_writes_trace(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    h0 = _host_log(tmp_path / "h0.jsonl", 0, epoch_time=10.0, stall=0.02,
                   frac=0.9)
    h1 = _host_log(tmp_path / "h1.jsonl", 1, epoch_time=12.0, stall=0.04,
                   frac=0.8)
    hb = str(tmp_path / "hb.h0.json")
    with open(hb, "w") as f:
        json.dump({"counter": 7, "epoch": 0, "step": 3, "phase": "train",
                   "ts": time.time()}, f)
    out = str(tmp_path / "pod_trace.json")
    rc = obs_main(["pod", h0, h1, "--heartbeat", hb,
                   "--heartbeat", str(tmp_path / "absent.json"),
                   "--trace-out", out])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "pod report — 2 host(s)" in printed
    assert "beat 7 at epoch 0 step 3" in printed
    assert "absent (clean exit or not started)" in printed
    trace = json.loads(open(out).read())
    assert {e["pid"] for e in trace["traceEvents"]} == {0, 1}
    assert obs_main(["pod", str(tmp_path / "missing.jsonl")]) == 2


# -- launcher heartbeat watchdog ---------------------------------------------


@pytest.mark.slow  # real multi-second watchdog waits; CI goodput step
# runs it without the slow filter (ISSUE 7 tier-1 budget)
def test_launch_watchdog_detects_and_kills_wedged_worker(tmp_path, capsys):
    """A worker that beats once then hangs (no crash, no preemption) must
    be detected, attributed to its position, and terminated — the
    pre-watchdog launcher waited forever."""
    from tpu_dist.cli.launch import main as launch_main

    hb_dir = str(tmp_path / "hb")
    # the child mimics a trainer far enough to take the injected flags,
    # write one heartbeat at a known position, then wedge
    child = (
        "import json, sys, time\n"
        "argv = sys.argv\n"
        "hb = argv[argv.index('--heartbeat_file') + 1]\n"
        "json.dump({'counter': 1, 'epoch': 2, 'step': 7, 'phase': 'train',\n"
        "           'ts': time.time()}, open(hb, 'w'))\n"
        "time.sleep(60)\n"
    )
    t0 = time.monotonic()
    rc = launch_main([
        "--nproc", "1", "--heartbeat_dir", hb_dir,
        "--watchdog_timeout", "2", "--watchdog_grace", "2", "--",
        sys.executable, "-c", child,
    ])
    took = time.monotonic() - t0
    assert rc != 0 and rc != 75  # a wedge is a failure, never requeue-me
    assert took < 30  # detected and killed, not waited out
    err = capsys.readouterr().err
    assert "WATCHDOG: worker 0 wedged" in err
    assert "epoch 2 step 7" in err and "'train'" in err
    assert "goodput loss" in err


def test_per_rank_path_one_scheme_for_all_sites():
    """The trainer (heartbeat + --per_host_log), the launcher watchdog,
    and `obs pod` all share ONE per-rank naming definition."""
    from tpu_dist.obs.heartbeat import per_rank_path

    assert per_rank_path("/d/hb.json", 0) == "/d/hb.json"
    assert per_rank_path("/d/hb.json", 3) == "/d/hb.json.h3"


@pytest.mark.slow  # ~6 s of real emergency-save sleeps; CI goodput
# step runs it without the slow filter (ISSUE 7 tier-1 budget)
def test_launch_watchdog_stands_down_during_preemption(tmp_path, capsys):
    """A preemption shutdown beats once ('preempted') then goes silent in
    the emergency save BY DESIGN — the watchdog must not reclassify that
    as a wedge and turn the requeue-75 exit into a crash. Child 0 exits
    75 immediately (setting the job's preempted state and triggering the
    SIGTERM fan-out); child 1 then stalls well past the watchdog timeout
    before finishing its graceful exit-75."""
    from tpu_dist.cli.launch import main as launch_main

    hb_dir = str(tmp_path / "hb")
    child = (
        "import json, signal, sys, time\n"
        "argv = sys.argv\n"
        "rank = int(argv[argv.index('--process_id') + 1])\n"
        "base = argv[argv.index('--heartbeat_file') + 1]\n"
        "hb = base if rank == 0 else base + '.h%d' % rank\n"
        "if rank == 0:\n"
        "    sys.exit(75)\n"
        "def on_term(s, f):\n"
        "    json.dump({'counter': 2, 'epoch': 0, 'step': 3,\n"
        "               'phase': 'preempted', 'ts': time.time()},\n"
        "              open(hb, 'w'))\n"
        "    time.sleep(6)\n"   # silent emergency save >> watchdog_timeout
        "    sys.exit(75)\n"
        "signal.signal(signal.SIGTERM, on_term)\n"
        "json.dump({'counter': 1, 'epoch': 0, 'step': 3, 'phase': 'train',\n"
        "           'ts': time.time()}, open(hb, 'w'))\n"
        "time.sleep(60)\n"
    )
    rc = launch_main([
        "--nproc", "2", "--heartbeat_dir", hb_dir,
        "--watchdog_timeout", "2", "--watchdog_grace", "1", "--",
        sys.executable, "-c", child,
    ])
    assert rc == 75                          # requeue-me, not a crash
    assert "WATCHDOG" not in capsys.readouterr().err


# -- e2e: the ledger invariant + triggered capture on a real run -------------


@pytest.mark.slow  # >10s e2e (full trainer fit + compiles): excluded from
# the timed tier-1 gate; gates in the CI goodput step, which runs this
# module without the slow filter
def test_e2e_goodput_buckets_sum_to_wallclock(tmp_path, capsys):
    """Acceptance: on a short run, every goodput window's buckets sum to
    its wall-clock exactly, and the run ledger's elapsed matches the
    measured Trainer-construction-to-exit wall time within 2%. The same
    run drives a manual --profile_steps capture end to end."""
    from tests.helpers import tiny_resnet
    from tpu_dist.config import TrainConfig
    from tpu_dist.obs.__main__ import main as obs_main
    from tpu_dist.train.trainer import Trainer, register_model

    register_model("tiny_gp_e2e", lambda num_classes=10: tiny_resnet(num_classes))
    log = str(tmp_path / "run.jsonl")
    prof_dir = str(tmp_path / "prof")
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_gp_e2e", num_classes=10,
        batch_size=64, epochs=2, steps_per_epoch=3, eval_every=1,
        synthetic_n=640, log_every=2, log_file=log,
        ckpt_dir=str(tmp_path / "ckpt"), save_every=1, seed=0,
        profile_dir=prof_dir, profile_steps="1:3",
    )
    t_wall0 = time.monotonic()
    Trainer(cfg).fit()
    wall = time.monotonic() - t_wall0
    records, bad = load_records(log)
    assert bad == 0
    windows = [r for r in records if r["kind"] == "goodput" and not r.get("final")]
    finals = [r for r in records if r["kind"] == "goodput" and r.get("final")]
    assert len(windows) == 3 and len(finals) == 1  # 2 epochs + tail
    for w in windows:
        parts = sum(w[f"{b}_s"] for b in goodput.ALL_BUCKETS)
        assert parts == pytest.approx(w["window_s"], abs=0.02)
    total = finals[0]
    parts = sum(total[f"{b}_s"] for b in goodput.ALL_BUCKETS)
    assert parts == pytest.approx(total["elapsed_s"], abs=0.05)
    # the acceptance tolerance: ledger elapsed vs measured wall within 2%
    # (+0.3s absolute: the __init__ lock preamble and post-fit teardown
    # sit outside the ledger's clock)
    assert total["elapsed_s"] == pytest.approx(wall, rel=0.02, abs=0.3)
    assert total["productive_s"] > 0
    assert total["compile_s"] > 0      # the jax.monitoring listener fed it
    assert total["ckpt_s"] > 0         # save_every=1 wrote checkpoints
    assert total["eval_s"] > 0
    assert 0.0 < total["goodput_frac"] <= 1.0
    # the manual capture ran: start+stop records and on-disk trace output
    profs = [r for r in records if r["kind"] == "profile"]
    events = [p.get("event") for p in profs]
    assert "start" in events and "stop" in events
    stop = next(p for p in profs if p.get("event") == "stop")
    assert stop["reason"] == "manual" and stop["steps"] == 2
    assert os.path.isdir(prof_dir) and os.listdir(prof_dir)
    # the CLI surfaces the ledger + capture in the report
    capsys.readouterr()
    assert obs_main(["summarize", log]) == 0
    text = capsys.readouterr().out
    assert "goodput (seconds per window):" in text
    assert "wall-clock productive" in text
    assert "profile: captured 2 step(s)" in text


@pytest.mark.slow  # two full trainer fits (~2 compiles): excluded from the
# timed tier-1 gate; runs in the CI goodput step and the full suite
def test_e2e_sigterm_resume_attributes_preempt_and_restart_loss(tmp_path):
    """Acceptance: a fault-plan SIGTERM run resumed from its snapshot
    shows nonzero preemption/restart loss in the folded run ledger."""
    from tests.helpers import tiny_resnet
    from tpu_dist.config import TrainConfig
    from tpu_dist.resilience.preemption import PreemptedError
    from tpu_dist.train.trainer import Trainer, register_model

    register_model("tiny_gp_pre", lambda num_classes=10: tiny_resnet(num_classes))
    log = str(tmp_path / "run.jsonl")
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_gp_pre", num_classes=10,
        batch_size=64, epochs=2, steps_per_epoch=3, eval_every=0,
        synthetic_n=640, log_every=2, log_file=log, seed=0,
        ckpt_dir=str(tmp_path / "ckpt"), save_every=1,
        fault_plan="sigterm@epoch=1:step=1",
    )
    with pytest.raises(PreemptedError):
        Trainer(cfg).fit()
    # requeued at identical size: same log_file, fresh run_id segment
    Trainer(cfg.replace(fault_plan=None, resume=True)).fit()
    records, _bad = load_records(log)
    led = goodput.run_ledger(records)
    assert led is not None and led["n_segments"] == 2
    assert led["preempt_s"] > 0           # SIGTERM tail + restart gap
    assert led["restart_gap_s"] > 0       # the second construction is real
    assert led["productive_s"] > 0
    report = summarize(records)
    assert report["goodput"]["n_segments"] == 2
