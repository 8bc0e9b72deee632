"""Run-telemetry subsystem (tpu_dist/obs): span tracing, counters,
heartbeat, straggler detection, the summarize/export-trace CLI, and the
TD106 telemetry-is-a-noop jaxpr gate."""

import json
import threading

import numpy as np
import pytest

from tpu_dist.obs import counters, spans
from tpu_dist.obs.heartbeat import Heartbeat, read as heartbeat_read
from tpu_dist.obs.straggler import epoch_skew
from tpu_dist.obs.summarize import (
    export_trace,
    format_text,
    load_records,
    summarize,
)


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


# -- spans ------------------------------------------------------------------


def test_span_nesting_and_chrome_export(tmp_path):
    spans.enable()
    with spans.span("outer", epoch=1):
        with spans.span("inner/a"):
            pass
        with spans.span("inner/b", step=2):
            pass
    evts = spans.events()
    by_name = {e["name"]: e for e in evts}
    assert set(by_name) == {"outer", "inner/a", "inner/b"}
    # complete events close innermost-first; nesting is interval containment
    outer, a, b = by_name["outer"], by_name["inner/a"], by_name["inner/b"]
    for inner in (a, b):
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert a["ts"] + a["dur"] <= b["ts"]  # sequential siblings stay ordered
    assert outer["args"] == {"epoch": 1}
    # export: structurally valid Chrome trace-event JSON (Perfetto contract:
    # top-level traceEvents list; each event name/ph/ts/dur/pid/tid)
    path = spans.export_chrome_trace(str(tmp_path / "trace.json"))
    trace = json.loads(open(path).read())
    assert isinstance(trace["traceEvents"], list) and len(trace["traceEvents"]) == 3
    for e in trace["traceEvents"]:
        assert e["ph"] == "X"
        assert isinstance(e["name"], str)
        for k in ("ts", "dur", "pid", "tid"):
            assert isinstance(e[k], (int, float)), (k, e)


def test_spans_disabled_record_nothing():
    with spans.span("nope"):
        pass
    spans.add_event("also_nope", 0.0, 1.0)
    assert spans.events() == []


def test_spans_drain_clears_and_caps(monkeypatch):
    spans.enable()
    for i in range(5):
        with spans.span(f"s{i}"):
            pass
    got = spans.drain()
    assert [e["name"] for e in got] == [f"s{i}" for i in range(5)]
    assert spans.events() == []
    # overflow: drops are counted, never silent
    monkeypatch.setattr(spans, "MAX_EVENTS", 2)
    for i in range(4):
        with spans.span(f"t{i}"):
            pass
    assert len(spans.events()) == 2
    assert spans.dropped() == 2
    assert spans.to_chrome_trace()["metadata"]["tpu_dist_dropped_events"] == 2


# -- counters ---------------------------------------------------------------


def test_counter_thread_safety_exact_totals():
    n_threads, n_incs = 8, 2000

    def worker():
        for _ in range(n_incs):
            counters.inc("t.hits")
            counters.add_seconds("t.secs", 0.001)

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert counters.get("t.hits") == n_threads * n_incs
    assert abs(counters.get("t.secs") - n_threads * n_incs * 0.001) < 1e-6


def test_counters_under_live_loader_producer():
    """The loader's producer THREAD writes the registry concurrently with
    the consumer; totals must come out exact."""
    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.data import DataLoader, DistributedSampler

    mesh = mesh_lib.data_parallel_mesh()
    n = 64
    images = np.random.default_rng(0).normal(size=(n, 4, 4, 3)).astype(np.float32)
    labels = np.zeros(n, np.int32)
    sampler = DistributedSampler(n, 1, 0, shuffle=False)
    loader = DataLoader(images, labels, 16, sampler, mesh)
    seen = 0
    for _ in range(2):  # two epochs: counters accumulate across iterations
        for _batch in loader:
            counters.inc("test.consumer_side")
            seen += 1
    assert counters.get("loader.batches_produced") == seen
    assert counters.get("loader.batches_consumed") == seen
    assert counters.get("test.consumer_side") == seen
    assert counters.get("loader.data_wait_s") >= 0.0


def test_counter_delta_and_gauges():
    counters.inc("a", 3)
    counters.set_gauge("mode", "int8")
    first = counters.snapshot()
    counters.inc("a", 2)
    counters.inc("b")
    d = counters.delta(first, counters.snapshot())
    assert d == {"a": 2, "b": 1}  # gauge strings and zero deltas omitted
    assert counters.snapshot()["mode"] == "int8"


# -- heartbeat --------------------------------------------------------------


def test_heartbeat_advances_and_sweeps(tmp_path):
    path = str(tmp_path / "hb" / "heartbeat.json")
    hb = Heartbeat(path, min_interval=0.0)
    assert hb.beat(epoch=0, step=1)
    first = heartbeat_read(path)
    assert first["counter"] == 1 and first["epoch"] == 0 and first["step"] == 1
    assert hb.beat(epoch=0, step=2)
    second = heartbeat_read(path)
    assert second["counter"] == 2 and second["mono_s"] >= first["mono_s"]
    hb.sweep()
    assert heartbeat_read(path) is None


def test_heartbeat_throttle_and_force(tmp_path):
    path = str(tmp_path / "hb.json")
    hb = Heartbeat(path, min_interval=3600.0)
    assert hb.beat(epoch=0, step=0)          # first write always lands
    assert not hb.beat(epoch=0, step=1)      # inside the throttle window
    assert heartbeat_read(path)["counter"] == 1
    assert hb.beat(epoch=0, step=2, force=True)  # force bypasses
    assert heartbeat_read(path)["counter"] == 3  # counter never skipped


@pytest.mark.slow  # >10s e2e (two trainer compiles): excluded from the
# timed tier-1 gate; the unit heartbeat tests above and the e2e summarize
# run below keep gate coverage of this subsystem
def test_trainer_heartbeat_step_grain_and_clean_exit_sweep(tmp_path):
    from tests.helpers import tiny_resnet
    from tpu_dist.config import TrainConfig
    from tpu_dist.train.trainer import Trainer, register_model

    register_model("tiny_obs_hb", lambda num_classes=10: tiny_resnet(num_classes))
    hb_path = str(tmp_path / "heartbeat.json")
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_obs_hb", num_classes=10,
        batch_size=64, epochs=1, steps_per_epoch=3, eval_every=0,
        synthetic_n=640, log_every=10, heartbeat_file=hb_path, seed=0,
    )
    trainer = Trainer(cfg)
    # step-grain advance: drive one epoch with the heartbeat attached
    trainer._heartbeat = Heartbeat(hb_path, min_interval=0.0)
    trainer.train_epoch(0)
    rec = heartbeat_read(hb_path)
    assert rec is not None and rec["counter"] == 3 and rec["step"] == 2
    # clean fit() exit sweeps the file — its absence is the "done" signal
    trainer._heartbeat = None
    trainer.fit()
    assert heartbeat_read(hb_path) is None


# -- straggler --------------------------------------------------------------


def test_straggler_skew_warning_multiprocess(capsys):
    """Multi-process epoch-skew detection via the injectable allgather:
    rows are per-process (epoch_time, stall_frac) exactly as a 4-host
    run's collective would return them."""
    rows = np.array([[10.0, 0.02], [10.2, 0.03], [25.0, 0.61], [9.9, 0.01]])
    rec = epoch_skew(10.0, 0.02, epoch=7, threshold=1.5, allgather=lambda row: rows)
    assert rec["straggler"] is True
    assert rec["worst_rank"] == 2
    assert rec["skew"] == pytest.approx(25.0 / np.median(rows[:, 0]), rel=1e-3)
    out = capsys.readouterr().out
    assert "straggler" in out and "process 2" in out and "(epoch 7)" in out
    assert counters.get("straggler.epochs_flagged") == 1


def test_straggler_quiet_when_balanced(capsys):
    rows = np.array([[10.0, 0.1], [10.5, 0.1], [9.8, 0.1]])
    rec = epoch_skew(10.0, 0.1, threshold=1.5, allgather=lambda row: rows)
    assert rec["straggler"] is False
    assert "straggler" not in capsys.readouterr().out


def test_straggler_single_process_trivial():
    rec = epoch_skew(12.5, 0.05, threshold=1.5)  # real (trivial) allgather
    assert rec["skew"] == 1.0 and rec["straggler"] is False
    assert rec["epoch_times"] == [12.5]


# -- MetricsHistory schema --------------------------------------------------


def test_history_schema_run_id_rel_s_and_counters(tmp_path):
    from tpu_dist.metrics.history import MetricsHistory

    counters.inc("x.hits", 4)
    path = str(tmp_path / "h.jsonl")
    with MetricsHistory(path, run_id="cfg1234-99") as h:
        h.log("train_epoch", epoch=0, loss=np.float32(1.5))
        counters.inc("x.hits")
        h.log("eval", epoch=0, top1=10.0)
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2
    for rec in lines:
        assert rec["schema_version"] == 15  # v15: causal decision tracing (ISSUE 19)
        assert rec["run_id"] == "cfg1234-99"
        assert isinstance(rec["rel_s"], float) and rec["rel_s"] >= 0
        assert "ts" in rec
    assert lines[0]["counters"]["x.hits"] == 4
    assert lines[1]["counters"]["x.hits"] == 5
    h.log("late", v=1)  # after close: silently disabled, never crashes
    assert len(open(path).readlines()) == 2


# -- StepTimer percentiles --------------------------------------------------


def test_step_timer_percentiles():
    from tpu_dist.obs.profile import StepTimer

    t = StepTimer(warmup_steps=1)
    t.tick()
    t.laps = [0.01 * (i + 1) for i in range(100)]  # deterministic laps
    p = t.percentiles()
    assert p["p50"] == pytest.approx(0.50)
    assert p["p95"] == pytest.approx(0.95)
    assert p["p99"] == pytest.approx(0.99)
    assert StepTimer(warmup_steps=5).percentiles() is None


# -- summarize / export-trace CLI ------------------------------------------


def _canned_jsonl(tmp_path):
    recs = [
        {"ts": 1.0, "rel_s": 5.0, "schema_version": 2, "run_id": "r-1",
         "kind": "train_epoch", "epoch": 0, "loss": 2.5,
         "epoch_time": 5.0, "images_per_sec": 1000.0,
         "step_time_p50": 0.010, "step_time_p95": 0.020,
         "step_time_p99": 0.040, "data_stall_frac": 0.25,
         "counters": {"ckpt.writes": 1, "loader.batches_consumed": 10}},
        {"ts": 2.0, "rel_s": 6.0, "schema_version": 2, "run_id": "r-1",
         "kind": "eval", "epoch": 0, "top1": 40.0, "top5": 80.0, "loss": 2.2},
        {"ts": 2.5, "rel_s": 8.0, "schema_version": 3, "run_id": "r-1",
         "kind": "device_stats", "epoch": 1, "step": 0,
         "grad_norm": 1.5, "param_norm": 12.0, "update_ratio": 0.003,
         "nonfinite_grads": 0.0},
        {"ts": 2.6, "rel_s": 9.0, "schema_version": 3, "run_id": "r-1",
         "kind": "device_stats", "epoch": 1, "step": 2,
         "grad_norm": 7.0, "param_norm": 12.1, "update_ratio": 0.009,
         "nonfinite_grads": 0.0},
        {"ts": 2.7, "rel_s": 9.1, "schema_version": 3, "run_id": "r-1",
         "kind": "anomaly", "epoch": 1, "step": 2,
         "anomaly": "grad_norm_explosion", "value": 7.0, "median": 1.5,
         "ratio": 4.667, "threshold": 4.0},
        {"ts": 3.0, "rel_s": 11.0, "schema_version": 3, "run_id": "r-1",
         "kind": "train_epoch", "epoch": 1, "loss": 2.0, "mfu": 0.42,
         "epoch_time": 4.0, "images_per_sec": 1250.0,
         "step_time_p50": 0.009, "step_time_p95": 0.015,
         "step_time_p99": 0.030, "data_stall_frac": 0.10,
         "counters": {"ckpt.writes": 3, "loader.batches_consumed": 20}},
        {"ts": 3.5, "rel_s": 11.2, "schema_version": 2, "run_id": "r-1",
         "kind": "straggler", "epoch": 1, "skew": 2.1, "worst_rank": 3,
         "max_s": 8.4, "median_s": 4.0},
        {"ts": 4.0, "rel_s": 12.0, "schema_version": 2, "run_id": "r-1",
         "kind": "spans",
         "events": [{"name": "ckpt/write", "ph": "X", "ts": 100.0,
                     "dur": 50.0, "pid": 0, "tid": 1}]},
    ]
    path = tmp_path / "run.jsonl"
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
        f.write('{"torn": tr')  # killed writer mid-line: tolerated
    return str(path)


def test_summarize_golden(tmp_path):
    path = _canned_jsonl(tmp_path)
    records, bad = load_records(path)
    assert len(records) == 8 and bad == 1
    report = summarize(records, bad)
    assert report["run_id"] == "r-1"
    assert report["totals"]["n_epochs"] == 2
    e0, e1 = report["epochs"]
    assert e0["images_per_sec"] == 1000.0 and e0["val_top1"] == 40.0
    assert e1["step_time_p99_s"] == 0.030 and e1["data_stall_frac"] == 0.10
    # counter deltas: first epoch from zero, second from the first snapshot
    assert e0["counter_deltas"] == {"ckpt.writes": 1, "loader.batches_consumed": 10}
    assert e1["counter_deltas"] == {"ckpt.writes": 2, "loader.batches_consumed": 10}
    assert report["stragglers"] == [
        {"epoch": 1, "skew": 2.1, "worst_rank": 3, "max_s": 8.4, "median_s": 4.0}
    ]
    # v3 health layer: per-epoch device_stats rollup, anomaly list, MFU
    assert "device_stats" not in e0 and e0["mfu"] is None
    assert e1["device_stats"] == {
        "samples": 2, "grad_norm_last": 7.0, "grad_norm_max": 7.0,
        "update_ratio_last": 0.009, "param_norm_last": 12.1,
    }
    assert e1["mfu"] == 0.42
    assert report["totals"]["mfu_mean"] == pytest.approx(0.42)
    assert report["anomalies"] == [{
        "epoch": 1, "step": 2, "anomaly": "grad_norm_explosion",
        "value": 7.0, "median": 1.5, "ratio": 4.667,
    }]
    text = format_text(report)
    assert "run r-1" in text and "1 unparsable line(s)" in text
    assert "straggler: epoch 1 process 3 at 2.1x median" in text
    assert "ckpt.writes+2" in text  # epoch-1 delta line
    assert "device: grad_norm last 7 / max 7" in text
    assert "anomaly: epoch 1 step 2 grad_norm_explosion value 7.0" in text
    assert "mean MFU 0.42" in text


def test_summarize_resets_deltas_at_resume_boundary():
    """Appending a resumed run (fresh run_id, fresh counter registry) to
    the same --log_file must not produce negative cross-run deltas."""
    records = [
        {"kind": "train_epoch", "epoch": 0, "run_id": "a-1",
         "epoch_time": 1.0, "counters": {"ckpt.writes": 5}},
        {"kind": "train_epoch", "epoch": 1, "run_id": "b-2",  # resumed
         "epoch_time": 1.0, "counters": {"ckpt.writes": 2}},
    ]
    report = summarize(records)
    e0, e1 = report["epochs"]
    assert e0["counter_deltas"] == {"ckpt.writes": 5}
    assert e1["counter_deltas"] == {"ckpt.writes": 2}  # NOT -3


def test_export_trace_offsets_resumed_run_segments():
    """A resumed run's restarted clock (fresh run_id, rel_s back to ~0)
    must be shifted past the first segment, not overlap it at ts≈0."""
    records = [
        {"kind": "train_epoch", "epoch": 0, "run_id": "a-1",
         "rel_s": 10.0, "epoch_time": 10.0},
        {"kind": "spans", "run_id": "a-1", "rel_s": 10.5,
         "events": [{"name": "ckpt/write", "ph": "X", "ts": 10.2e6,
                     "dur": 1e5, "pid": 0, "tid": 1}]},
        {"kind": "train_epoch", "epoch": 1, "run_id": "b-2",  # resumed
         "rel_s": 8.0, "epoch_time": 8.0},
    ]
    trace = export_trace(records)
    by_name = {e["name"]: e for e in trace["traceEvents"]}
    assert by_name["train_epoch/0"]["ts"] == pytest.approx(0.0)
    # segment b starts after everything in segment a (>= 10.5s here)
    resumed = by_name["train_epoch/1"]
    assert resumed["ts"] >= 10.5e6
    assert resumed["ts"] + resumed["dur"] >= 18.0e6


def test_summarize_cli_json_and_export_trace(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    path = _canned_jsonl(tmp_path)
    assert obs_main(["summarize", path, "--format", "json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["totals"]["n_epochs"] == 2
    out = str(tmp_path / "trace.json")
    assert obs_main(["export-trace", path, "-o", out]) == 0
    trace = json.loads(open(out).read())
    names = [e["name"] for e in trace["traceEvents"]]
    assert "ckpt/write" in names          # spans record passed through
    assert "train_epoch/0" in names       # synthesized epoch bar
    for e in trace["traceEvents"]:        # structurally Perfetto-loadable
        assert e["ph"] == "X" and isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float))
    # epoch bar reconstructed from rel_s: ends at rel_s, spans epoch_time
    bar = next(e for e in trace["traceEvents"] if e["name"] == "train_epoch/0")
    assert bar["ts"] == pytest.approx(0.0) and bar["dur"] == pytest.approx(5.0e6)
    assert obs_main(["summarize", str(tmp_path / "missing.jsonl")]) == 2


# -- TD106 + fetch-count parity --------------------------------------------


def test_td106_telemetry_noop_gate():
    from tpu_dist.analysis.jaxpr_audit import telemetry_noop_violations

    assert telemetry_noop_violations() == []


def test_td106_rule_registered():
    from tpu_dist.analysis.rules import RULES

    assert "TD106" in RULES and "TD007" in RULES


@pytest.mark.slow  # >10s e2e (two full fits): excluded from the timed
# tier-1 gate; runs in the CI observability step and the full suite
def test_trainer_fetch_count_unchanged_by_telemetry(tmp_path, monkeypatch):
    """Arming spans/counters/heartbeat must not add per-step device
    transfers: the _fetch_metrics call count is identical telemetry-on vs
    telemetry-off (acceptance criterion of the obs subsystem)."""
    from tests.helpers import tiny_resnet
    from tpu_dist.config import TrainConfig
    from tpu_dist.train import trainer as trainer_mod

    trainer_mod.register_model(
        "tiny_obs_fetch", lambda num_classes=10: tiny_resnet(num_classes)
    )
    calls = []
    real_fetch = trainer_mod._fetch_metrics
    monkeypatch.setattr(
        trainer_mod, "_fetch_metrics",
        lambda m: (calls.append(1), real_fetch(m))[1],
    )
    counts = []
    for armed in (False, True):
        calls.clear()
        cfg = TrainConfig(
            dataset="synthetic", model="tiny_obs_fetch", num_classes=10,
            batch_size=64, epochs=1, steps_per_epoch=4, eval_every=0,
            synthetic_n=640, log_every=2, seed=0,
            log_file=str(tmp_path / "armed.jsonl") if armed else None,
            heartbeat_file=str(tmp_path / "hb.json") if armed else None,
        )
        trainer_mod.Trainer(cfg).fit()
        counts.append(len(calls))
    assert counts[0] == counts[1], counts


# -- e2e: acceptance run ----------------------------------------------------


@pytest.mark.slow  # ~10 s full-fit e2e; CI observability step runs it
# without the slow filter (ISSUE 7 tier-1 budget)
def test_e2e_short_run_summarize_reports_everything(tmp_path, capsys):
    """The acceptance path: a short CPU run with --log_file, then
    `python -m tpu_dist.obs summarize` reports per-epoch throughput,
    p50/p95/p99, stall fraction, and counter deltas; export-trace output
    is valid trace-event JSON."""
    from tests.helpers import tiny_resnet
    from tpu_dist.config import TrainConfig
    from tpu_dist.obs.__main__ import main as obs_main
    from tpu_dist.train.trainer import Trainer, register_model

    register_model("tiny_obs_e2e", lambda num_classes=10: tiny_resnet(num_classes))
    log = str(tmp_path / "run.jsonl")
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_obs_e2e", num_classes=10,
        batch_size=64, epochs=2, steps_per_epoch=3, eval_every=1,
        synthetic_n=640, log_every=2, log_file=log,
        ckpt_dir=str(tmp_path / "ckpt"), save_every=1, seed=0,
    )
    Trainer(cfg).fit()
    capsys.readouterr()
    assert obs_main(["summarize", log, "--format", "json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["totals"]["n_epochs"] == 2
    for row in report["epochs"]:
        assert row["images_per_sec"] > 0
        assert row["step_time_p50_s"] > 0
        assert row["step_time_p95_s"] >= row["step_time_p50_s"]
        assert row["step_time_p99_s"] >= row["step_time_p95_s"]
        assert 0.0 <= row["data_stall_frac"] < 1.0
        assert row["counter_deltas"]["train.steps"] == 3
    # the checkpoint writes show up as counter deltas
    total_ckpt = sum(
        r["counter_deltas"].get("ckpt.writes", 0) for r in report["epochs"]
    )
    assert total_ckpt >= 1
    out = str(tmp_path / "trace.json")
    assert obs_main(["export-trace", log, "-o", out]) == 0
    trace = json.loads(open(out).read())
    assert len(trace["traceEvents"]) > 0
    names = {e["name"] for e in trace["traceEvents"]}
    assert "train/dispatch" in names or "train/compile+dispatch" in names
    assert "ckpt/write" in names


# -- the input path and the epoch boundary (ISSUE 24) -------------------------


def _by_name(evts):
    out = {}
    for e in evts:
        out.setdefault(e["name"], []).append(e)
    return out


def _at(evts):
    return {(e["args"]["epoch"], e["args"]["step"]) for e in evts}


def test_add_timed_feeds_the_counter_always_and_the_span_when_on():
    import time

    t0 = time.perf_counter()
    t1 = spans.add_timed("x/region", "x.region_s", t0, epoch=3)
    assert t1 >= t0 and counters.get("x.region_s") == pytest.approx(t1 - t0)
    assert spans.events() == []  # recorder off: the counter alone
    spans.enable()
    t2 = spans.add_timed("x/region", "x.region_s", t1, epoch=3)
    spans.add_timed("x/uncounted", None, t2)
    assert counters.get("x.region_s") == pytest.approx(t2 - t0)
    (ev, _) = spans.events()
    assert ev["name"] == "x/region" and ev["args"] == {"epoch": 3}
    assert ev["dur"] == pytest.approx((t2 - t1) * 1e6, abs=0.2)


def test_clock_anchor_roundtrips_through_chrome_trace():
    import time

    spans.enable()
    lo = time.time_ns()
    t = time.perf_counter()
    spans.add_event("tick", t, 0.0)
    hi = time.time_ns()
    anchor = spans.clock_anchor()
    trace = json.loads(json.dumps(spans.to_chrome_trace()))
    assert trace["metadata"]["tpu_dist_clock_anchor"] == anchor
    # an event's ts (us since the origin) lands on the wall clock through it
    unix_ns = anchor["unix_ns_at_origin"] + trace["traceEvents"][0]["ts"] * 1e3
    assert lo - 1e6 <= unix_ns <= hi + 1e6  # perf_counter vs time_ns: < 1 ms
    # fresh=False keeps origin and anchor (the TD106 audit re-arms this way)
    spans.enable(fresh=False)
    assert spans.clock_anchor() == anchor


def test_loader_producer_split_counters_and_spans():
    """gather / h2d / queue_full are timed on the producer thread, always
    into counters and, recorder on, into spans keyed by (epoch, step)."""
    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.data import DataLoader, DistributedSampler

    n, bs = 64, 16
    images = np.random.default_rng(0).normal(size=(n, 4, 4, 3)).astype(np.float32)
    labels = np.zeros(n, np.int32)
    sampler = DistributedSampler(n, 1, 0, shuffle=False)
    loader = DataLoader(images, labels, bs, sampler, mesh_lib.data_parallel_mesh())
    sampler.set_epoch(0)
    assert len(list(loader)) == 4
    assert spans.events() == []  # recorder off
    for name in ("loader.gather_s", "loader.h2d_s"):
        assert counters.get(name) > 0, name
    assert counters.get("loader.h2d_bytes") == images.nbytes + labels.nbytes
    spans.enable()
    sampler.set_epoch(5)
    for _ in loader.iter_from(1):  # mid-epoch resume keeps the batch's index
        pass
    got = _by_name(spans.events())
    assert set(got) == {"loader/gather", "loader/h2d", "loader/queue_full"}
    for evts in got.values():
        assert _at(evts) == {(5, 1), (5, 2), (5, 3)}
    # one clock read per boundary: the three regions tile the thread's life
    evts = sorted(spans.events(), key=lambda e: e["ts"])
    for a, b in zip(evts, evts[1:]):
        assert b["ts"] == pytest.approx(a["ts"] + a["dur"], abs=0.3)


@pytest.fixture(scope="module")
def boundary_trainer():
    """One micro-model Trainer for the epoch-boundary tests (its build and
    first compile are most of their cost)."""
    from tests.helpers import TinyConvNet
    from tpu_dist.config import TrainConfig
    from tpu_dist.train import trainer as trainer_mod

    trainer_mod.register_model(
        "tiny_obs_boundary", lambda num_classes=10: TinyConvNet(num_classes)
    )
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_obs_boundary", num_classes=10,
        batch_size=64, epochs=8, eval_every=0, synthetic_n=320, log_every=2,
        seed=0,
    )
    return trainer_mod.Trainer(cfg)


BOUNDARY_COUNTERS = (
    "train.epoch_head_s", "train.epoch_refill_s", "train.epoch_drain_s",
    "train.epoch_tail_s",
)


def test_train_epoch_boundary_spans_join_and_add_up(boundary_trainer, capsys):
    spans.enable()
    for epoch in (0, 1):
        assert boundary_trainer.train_epoch(epoch)["steps"] == 5
    for name in BOUNDARY_COUNTERS + ("loader.gather_s", "loader.h2d_s"):
        assert counters.get(name) > 0, name
    got = _by_name(spans.events())
    for name, evts in got.items():  # every span says where it belongs
        assert all("epoch" in e["args"] for e in evts), name
    # (epoch, step) joins the producer thread's batch to the loop's step
    steps = {(e, s) for e in (0, 1) for s in range(5)}
    dispatches = got["train/dispatch"] + got.get("train/compile+dispatch", [])
    for evts in (got["train/data_wait"], dispatches):
        assert _at(evts) == steps
    # the producer's side holds one batch more: epoch 1's producer went on to
    # gather and place epoch 2's first batch (the look-ahead, which may still
    # be in flight here); epoch 1's own batch 0 was made by epoch 0's thread
    for evts in (got["loader/gather"], got["loader/h2d"]):
        assert steps <= _at(evts) <= steps | {(2, 0)}
    assert _at(got["train/host_fetch"]) == {(e, s) for e in (0, 1) for s in (0, 2, 4)}
    assert {e["tid"] for e in got["loader/h2d"]}.isdisjoint(
        e["tid"] for e in got["train/data_wait"]
    )
    whole = sorted(got["train/epoch"], key=lambda e: e["ts"])
    assert [e["args"] for e in whole] == [{"epoch": 0}, {"epoch": 1}]
    covered = []
    for w in whole:
        kids = [
            e for e in spans.events()
            if e["tid"] == w["tid"] and e is not w and e["name"] != "train/epoch"
            and e["args"]["epoch"] == w["args"]["epoch"]
        ]
        names = {e["name"] for e in kids}
        assert names >= {"train/epoch_head", "train/data_wait", "train/host_fetch",
                         "train/epoch_drain", "train/epoch_tail"}
        for e in kids:  # nesting by containment
            assert w["ts"] - 0.2 <= e["ts"]
            assert e["ts"] + e["dur"] <= w["ts"] + w["dur"] + 0.2, e["name"]
        covered.append(sum(e["dur"] for e in kids) / w["dur"])
    # head + waits + dispatches + fetches + drain + tail is the epoch, but
    # for the Python between them (epoch 0 also holds the one cost capture)
    assert covered[0] <= 1.001 and 0.9 <= covered[1] <= 1.001, covered


def test_train_epoch_recorder_off_counts_and_records_nothing(boundary_trainer, capsys):
    out = boundary_trainer.train_epoch(2)
    assert spans.events() == []
    for name in BOUNDARY_COUNTERS:
        assert counters.get(name) > 0, name
    # the first wait of the epoch is its refill, and is part of data_wait_s
    assert counters.get("train.epoch_refill_s") <= out["data_wait_s"] + 1e-4
    parts = sum(counters.get(name) for name in BOUNDARY_COUNTERS)
    assert parts < out["epoch_time"]


def test_epoch_mfu_is_flops_over_epoch_wall(boundary_trainer, monkeypatch, capsys):
    """The epoch's MFU divides by wall time per step (the drain makes it the
    device's), not by the p50 lap between dispatches, which under run-ahead
    is host time and read 300-900% on the chip."""
    from tpu_dist.obs import costmodel

    tr = boundary_trainer
    warm = tr.train_epoch(3)  # the first dispatch captured the step's cost
    flops = tr._step_cost["flops_per_step"]
    # a stub peak that puts this machine's MFU near 1, clear of the rounding
    peak = flops * warm["steps"] / warm["epoch_time"] / tr.n_devices
    monkeypatch.setattr(costmodel, "chip_peak_flops", lambda kind=None: peak)
    compile_s = counters.get("compile.seconds")
    out = tr.train_epoch(4)
    wall = out["epoch_time"] - (counters.get("compile.seconds") - compile_s)
    want = flops * out["steps"] / wall / (peak * tr.n_devices)
    assert out["mfu"] == pytest.approx(want, rel=2e-3)
    assert "MFU" in capsys.readouterr().out
