"""Serving subsystem tests (ISSUE 13, ``docs/serving.md``).

Covers: streaming latency-histogram units (buckets / merge / quantile
bounds / serialization), queue+batcher determinism on the injectable
clock, bucket-ladder retrace-freedom via ``CompileWatcher`` (and the
watcher's new ``baseline()``/in-watcher-warning contract), checkpoint →
serving-weights round-trips through the elastic ``Remapper``, SLO rule
fire/sustain/cooldown, the OpenMetrics histogram grammar round-trip
through ``export.parse``, the ``obs compare --slo`` exit contract, the
TD114 gate + registry, schema-v10 ``serve`` record rendering in
summarize/tail, and (slow) the full ``make serve-drill`` e2e plus the
``bench.py --serve`` record shape.
"""

from __future__ import annotations

import json
import re

import numpy as np
import pytest

from tpu_dist.obs import counters as counters_lib
from tpu_dist.obs import export as export_lib
from tpu_dist.serve import slo as slo_lib
from tpu_dist.serve.drill import (
    IMAGE_SHAPE,
    ManualClock,
    _drill_model,
    replay,
    write_training_ckpt,
)
from tpu_dist.serve.engine import (
    ServingEngine,
    batch_buckets,
    bucket_for,
    dequantize_weights,
    load_serving_state,
    quantize_weights,
)


class _TinyMLP:
    """Smallest model with the nn contract (init/apply → (logits, state))
    — engine tests must not pay a ResNet compile per case."""

    classes = 10

    def init(self, key):
        import jax
        import jax.numpy as jnp

        k1, k2 = jax.random.split(key)
        d = int(np.prod(IMAGE_SHAPE))
        params = {
            "w1": jax.random.normal(k1, (d, 16), jnp.float32) * 0.05,
            "b1": jnp.zeros((16,), jnp.float32),
            "w2": jax.random.normal(k2, (16, self.classes), jnp.float32) * 0.05,
            "b2": jnp.zeros((self.classes,), jnp.float32),
        }
        return params, {}

    def apply(self, params, state, x, *, train=False, axis_name=None, **kw):
        import jax.numpy as jnp

        h = jnp.maximum(
            x.reshape(x.shape[0], -1) @ params["w1"] + params["b1"], 0.0
        )
        return h @ params["w2"] + params["b2"], state


def _mlp_engine(**kw):
    import jax

    model = _TinyMLP()
    params, bn = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, bn, max_batch=kw.pop("max_batch", 4), **kw)
    return model, eng


@pytest.fixture(autouse=True)
def _fresh_registry():
    counters_lib.reset()
    yield
    counters_lib.reset()


# -- histogram units ---------------------------------------------------------


def test_histogram_buckets_and_sum_count():
    h = slo_lib.LatencyHistogram()
    for v in (0.0, 5e-5, 1e-4, 2e-4, 0.5):
        h.observe(v)
    assert h.count == 5 and sum(h.counts) == 5
    # le-semantics: 1e-4 lands in the FIRST bucket (v <= edge)
    assert h.counts[0] == 3
    assert h.min == 0.0 and h.max == 0.5
    assert h.sum == pytest.approx(0.50035, abs=1e-9)


def test_histogram_quantile_bound_is_conservative():
    h = slo_lib.LatencyHistogram()
    for v in (0.001, 0.001, 0.001, 0.1):
        h.observe(v)
    p50 = h.quantile_bound(0.5)
    assert p50 is not None and p50 >= 0.001  # upper bound, never under
    # one bucket of slack at most: 0.001 sits in bucket le=0.0016
    assert p50 <= 0.0016000000000000003
    # overflow bucket returns the exact max
    h.observe(1e9)
    assert h.quantile_bound(1.0) == 1e9
    assert slo_lib.LatencyHistogram().quantile_bound(0.5) is None
    with pytest.raises(ValueError):
        h.quantile_bound(1.5)


def test_histogram_merge_and_layout_refusal():
    a, b = slo_lib.LatencyHistogram(), slo_lib.LatencyHistogram()
    for v in (0.001, 0.01):
        a.observe(v)
    for v in (0.1, 1.0):
        b.observe(v)
    a.merge(b)
    assert a.count == 4
    assert a.sum == pytest.approx(1.111)
    assert a.min == 0.001 and a.max == 1.0
    with pytest.raises(ValueError):
        a.merge(slo_lib.LatencyHistogram(edges=(0.1, 1.0)))


def test_histogram_dict_roundtrip_compact():
    h = slo_lib.LatencyHistogram()
    for v in (0.002, 0.002, 0.3):
        h.observe(v)
    d = h.to_dict()
    # compact: only the two non-zero buckets serialize
    assert len(d["buckets"]) == 2
    h2 = slo_lib.LatencyHistogram.from_dict(d)
    assert h2.counts == h.counts and h2.count == h.count
    assert h2.quantile_bound(0.5) == h.quantile_bound(0.5)
    with pytest.raises(ValueError):
        slo_lib.LatencyHistogram.from_dict({"edges": 3, "count": 0})
    # corrupt bucket indices must refuse, not write out of range (or
    # silently into the overflow bucket via a negative index)
    for bad in ("99", "-1"):
        with pytest.raises(ValueError):
            slo_lib.LatencyHistogram.from_dict(
                {"edges": len(slo_lib.DEFAULT_EDGES),
                 "buckets": {bad: 1}, "count": 1}
            )


def test_serve_report_skips_corrupt_latency_hist(tmp_path):
    """One torn/corrupt latency_hist record must not crash the report
    CLI — the loader's skip-and-continue discipline."""
    log = _serve_log(tmp_path / "s.jsonl", 10.0, 20.0, 100.0, "r")
    with open(log, "a") as f:
        f.write(json.dumps({
            "ts": 9.0, "rel_s": 9.0, "schema_version": 10, "kind": "serve",
            "run_id": "r", "window_s": 1.0, "completed": 1,
            "latency_hist": {"edges": 22, "buckets": {"99": 1}, "count": 1},
        }) + "\n")
    from tpu_dist.obs.summarize import load_records

    records, _ = load_records(log)
    rep = slo_lib.serve_report(records)
    assert rep["n_windows"] == 4  # the corrupt hist is skipped, not fatal
    assert slo_lib.format_report_text(rep)


# -- buckets -----------------------------------------------------------------


def test_bucket_ladder_and_lookup():
    assert batch_buckets(8) == (1, 2, 4, 8)
    assert bucket_for(1, (1, 2, 4, 8)) == 1
    assert bucket_for(3, (1, 2, 4, 8)) == 4
    assert bucket_for(8, (1, 2, 4, 8)) == 8
    with pytest.raises(ValueError):
        batch_buckets(6)  # non-power-of-two ladder top
    with pytest.raises(ValueError):
        bucket_for(9, (1, 2, 4, 8))


# -- engine: determinism, retrace freedom, invariants ------------------------


def test_engine_replay_is_deterministic(tmp_path):
    """Two replays of the same trace on the manual clock produce
    IDENTICAL serving telemetry — histograms, occupancy, queue depths,
    and the serve records (modulo wall-clock stamps)."""
    import jax

    model = _TinyMLP()
    params, bn = model.init(jax.random.PRNGKey(0))
    weights = {"params": params, "bn_state": bn}
    outs = [
        replay(str(tmp_path), f"run{i}", model, weights, auto_step_s=0.0005)
        for i in (0, 1)
    ]
    s0, s1 = outs[0]["stats"], outs[1]["stats"]
    assert s0.total.counts == s1.total.counts
    assert s0.total.sum == pytest.approx(s1.total.sum, abs=1e-12)
    assert s0.queue_depth_max == s1.queue_depth_max
    assert s0.batches == s1.batches
    assert s0.occupancy_sum == pytest.approx(s1.occupancy_sum)
    recs = []
    for i in (0, 1):
        with open(outs[i]["log"]) as f:
            recs.append([
                json.loads(l) for l in f
                if json.loads(l).get("kind") == "serve"
            ])
    drop = ("ts", "rel_s", "run_id", "counters")
    a = [{k: v for k, v in r.items() if k not in drop} for r in recs[0]]
    b = [{k: v for k, v in r.items() if k not in drop} for r in recs[1]]
    assert a == b and a  # identical windows, and there were some


def test_engine_zero_retraces_on_bucket_ladder_then_detects_drift(tmp_path):
    from tpu_dist.metrics.history import MetricsHistory

    hist = MetricsHistory(str(tmp_path / "s.jsonl"), run_id="rt")
    model, eng = _mlp_engine(history=hist)
    eng.warmup(IMAGE_SHAPE)
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 4, 4, 1):  # every bucket, repeatedly
        for _ in range(n):
            eng.submit(rng.standard_normal(IMAGE_SHAPE).astype(np.float32))
        done = eng.pump()
        assert len(done) == n
        assert all(r.result.shape == (10,) for r in done)
    assert counters_lib.get("compile.retraces") == 0
    assert eng.stats.check_invariants() == []
    # an off-ladder payload shape IS a retrace — counted, evented (same
    # element count so the MLP still runs; the AVAL is what drifted)
    eng.submit(rng.standard_normal((int(np.prod(IMAGE_SHAPE)),))
               .astype(np.float32))
    eng.pump()
    assert counters_lib.get("compile.retraces") == 1
    assert counters_lib.get("serve.retraces") == 1
    hist.close()
    recs = [json.loads(l) for l in open(tmp_path / "s.jsonl")]
    events = [r for r in recs if r.get("kind") == "serve" and r.get("event")]
    assert events and events[0]["event"] == "retrace"


def test_engine_phase_split_partitions_total():
    model, eng = _mlp_engine(clock=ManualClock(auto_step_s=0.001))
    eng.warmup(IMAGE_SHAPE)
    for i in range(3):
        eng.submit(np.zeros(IMAGE_SHAPE, np.float32), arrival_s=0.0)
    (done) = eng.pump()
    for r in done:
        assert r.total_s == pytest.approx(sum(r.phase_s.values()), abs=1e-9)
        assert r.ttfb_s <= r.total_s
        assert r.phase_s["queue_wait"] >= 0
    assert eng.stats.check_invariants() == []
    # a FUTURE-dated arrival (replay that didn't advance its clock, or a
    # frontend on another clock origin) clamps consistently: the phase
    # split must still partition the total, not overshoot it
    eng.submit(np.zeros(IMAGE_SHAPE, np.float32), arrival_s=1e9)
    (late,) = eng.pump()
    assert late.phase_s["queue_wait"] == 0.0
    assert late.total_s == pytest.approx(sum(late.phase_s.values()), abs=1e-9)
    assert eng.stats.check_invariants() == []


def test_compile_watcher_baseline_and_in_watcher_warning(capsys):
    from tpu_dist.obs.costmodel import CompileWatcher

    class Stub:
        def __init__(self):
            self.n = 0

        def _cache_size(self):
            return self.n

    stub = Stub()
    w = CompileWatcher(stub, name="stub step")
    stub.n = 4  # warmup compiled 4 bucket signatures
    assert w.baseline() == 4
    assert counters_lib.get("compile.events") == 4
    assert counters_lib.get("compile.retraces") == 0
    assert w.observe() is False  # steady state
    stub.n = 5
    assert w.observe(context="epoch 1 step 2") is True
    assert counters_lib.get("compile.retraces") == 1
    out = capsys.readouterr().out
    assert "stub step RECOMPILED at epoch 1 step 2" in out
    # without baseline(): the first observation's first compile is free
    counters_lib.reset()
    stub2 = Stub()
    w2 = CompileWatcher(stub2, warn=False)
    stub2.n = 1
    assert w2.observe() is False
    stub2.n = 2
    assert w2.observe() is True
    assert counters_lib.get("compile.retraces") == 1


# -- checkpoint → serving weights --------------------------------------------


def test_serving_restore_through_remapper_bit_exact(tmp_path):
    """A dp=4 ZeRO-1 training checkpoint loads onto the 1-process
    serving extent THROUGH the elastic Remapper, params/bn bit-exact."""
    import jax

    model = _drill_model()
    saved = write_training_ckpt(str(tmp_path / "ck"), model, dp=4)
    out = load_serving_state(str(tmp_path / "ck"), model)
    assert [k for k, kind in out["remapped"] if kind == "zero1_flat"]
    for pa, la in zip(
        jax.tree_util.tree_leaves(saved["params"]),
        jax.tree_util.tree_leaves(out["params"]),
    ):
        assert np.array_equal(np.asarray(pa), np.asarray(la))
    for pa, la in zip(
        jax.tree_util.tree_leaves(saved["bn_state"]),
        jax.tree_util.tree_leaves(out["bn_state"]),
    ):
        assert np.array_equal(np.asarray(pa), np.asarray(la))
    assert out["step"] == 120 and out["epoch"] == 3
    assert counters_lib.get("serve.weights_remapped") == 1


def test_serving_restore_per_leaf_momentum_no_remap(tmp_path):
    """A plain-SGD checkpoint (per-leaf momentum tree, no flat layout)
    loads verbatim — the opt subtree is mirrored, nothing remaps."""
    import jax

    from tpu_dist import ckpt as ckpt_lib
    from tpu_dist.train.state import TrainState

    model = _TinyMLP()
    params, bn = model.init(jax.random.PRNGKey(3))
    mom = jax.tree_util.tree_map(lambda a: np.asarray(a) * 0 + 0.5, params)
    state = TrainState(params=params, bn_state=bn, opt_state=mom,
                       step=np.asarray(7, np.int32))
    ckpt_lib.save(str(tmp_path / "ck"), state, epoch=1)
    out = load_serving_state(str(tmp_path / "ck"), model)
    assert out["remapped"] == []
    for pa, la in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(out["params"]),
    ):
        assert np.array_equal(np.asarray(pa), np.asarray(la))


def test_serving_restore_quarantines_corrupt_newest(tmp_path):
    """The ladder discipline: a corrupt newest checkpoint is quarantined
    and the older one serves."""
    import os

    import shutil

    model = _drill_model()
    ckdir = str(tmp_path / "ck")
    saved = write_training_ckpt(ckdir, model, dp=2)
    # "newest" = a truncated copy (a torn write: the archive directory is
    # gone — exactly what the ladder's CKPT_READ_ERRORS quarantine)
    newest = os.path.join(ckdir, "ckpt_9.npz")
    shutil.copy(saved["path"], newest)
    size = os.path.getsize(newest)
    with open(newest, "r+b") as f:
        f.truncate(size // 2)
    out = load_serving_state(ckdir, model)
    assert out["epoch"] == 3
    assert not os.path.exists(newest)  # moved aside
    assert os.path.exists(newest + ".corrupt")


def test_serving_restore_refuses_wrong_model(tmp_path):
    from tpu_dist.elastic.errors import ConfigMismatchError

    write_training_ckpt(str(tmp_path / "ck"), _drill_model(), dp=2)
    with pytest.raises((ConfigMismatchError, KeyError)):
        load_serving_state(str(tmp_path / "ck"), _TinyMLP())


# -- int8 weight quantization ------------------------------------------------


def test_quantized_weights_roundtrip_and_serve():
    import jax

    model = _TinyMLP()
    params, bn = model.init(jax.random.PRNGKey(0))
    q, shapes = quantize_weights(params)
    back = dequantize_weights(q, shapes)
    for orig, deq in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(back)
    ):
        orig = np.asarray(orig)
        deq = np.asarray(deq).reshape(orig.shape)
        # per-chunk symmetric int8: error bounded by scale/2 per element
        bound = np.abs(orig).max() / 127.0 * 0.5 + 1e-9
        assert np.max(np.abs(orig - deq)) <= bound * 2
    eng = ServingEngine(model, params, bn, max_batch=2, quantize=True)
    eng.warmup(IMAGE_SHAPE)
    eng.submit(np.zeros(IMAGE_SHAPE, np.float32))
    done = eng.pump()
    assert done[0].result.shape == (10,)
    assert np.all(np.isfinite(done[0].result))
    assert counters_lib.get("compile.retraces") == 0


# -- SLO rules ---------------------------------------------------------------


def test_slo_rule_fire_sustain_cooldown():
    from tpu_dist.obs.alerts import AlertRule

    eng = slo_lib.make_slo_engine([
        AlertRule("p99", "serve.latency_p99_ms", ">", 100.0,
                  sustain=2, cooldown=1),
    ])
    breach = {"serve.latency_p99_ms": 250.0}
    calm = {"serve.latency_p99_ms": 10.0}
    assert eng.observe(breach) == []          # streak 1 < sustain
    assert len(eng.observe(breach)) == 1      # sustained → fires
    assert eng.active() == {"p99": 1.0}
    assert eng.observe(breach) == []          # cooldown drains
    assert len(eng.observe(breach)) == 1      # re-fires after cooldown
    assert eng.observe(calm) == []
    assert eng.active() == {"p99": 0.0}


def test_slo_retrace_delta_rule_fires_on_first_retrace():
    eng = slo_lib.make_slo_engine(slo_lib.load_slo_rules("default"))
    win = {"compile.retraces": 0.0}
    assert not [a for a in eng.observe(win) if a["rule"] == "serve_retrace"]
    win = {"compile.retraces": 1.0}
    fired = [a for a in eng.observe(win) if a["rule"] == "serve_retrace"]
    assert fired and fired[0]["delta"] is True


def test_load_slo_rules_specs(tmp_path):
    rules = slo_lib.load_slo_rules("default")
    assert {r.name for r in rules} >= {"slo_p99_high", "serve_retrace"}
    spec = tmp_path / "slo.toml"
    spec.write_text(
        '[[rule]]\nbuiltin = "slo_p99_high"\nthreshold = 50.0\n'
        '[[rule]]\nname = "q"\nmetric = "serve.queue_depth"\n'
        'op = ">"\nthreshold = 10\n'
    )
    loaded = slo_lib.load_slo_rules(str(spec))
    assert loaded[0].name == "slo_p99_high" and loaded[0].threshold == 50.0
    assert loaded[1].metric == "serve.queue_depth"
    bad = tmp_path / "bad.toml"
    bad.write_text('[[rule]]\nbuiltin = "no_such_slo"\n')
    with pytest.raises(ValueError):
        slo_lib.load_slo_rules(str(bad))


# -- exposition histogram grammar --------------------------------------------


def test_exposition_histogram_grammar_roundtrip():
    st = slo_lib.ServeStats()
    st.on_batch(2, 2)
    for v in (0.002, 0.004, 0.05):
        st.on_request_done(v, v / 2, {p: v / 10 for p in slo_lib.PHASES})
    text = export_lib.render(
        {"serve.requests": 3}, histograms=st.histogram_families()
    )
    fam = export_lib.metric_name("serve.latency_seconds")
    # grammar: TYPE line, le-labelled cumulative buckets ending at +Inf,
    # then _sum and _count
    assert f"# TYPE {fam} histogram" in text
    bucket_lines = [
        l for l in text.splitlines() if l.startswith(fam + "_bucket")
    ]
    assert bucket_lines[-1].startswith(fam + '_bucket{le="+Inf"}')
    for line in bucket_lines:
        assert re.match(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*_bucket\{le="[^"]+"\} \d+$', line
        ), line
    parsed = export_lib.parse(text)
    assert parsed[fam + "_count"] == 3
    assert parsed[fam + "_sum"] == pytest.approx(0.056)
    # cumulative monotone, +Inf equals count
    cums = [float(l.rsplit(" ", 1)[1]) for l in bucket_lines]
    assert cums == sorted(cums) and cums[-1] == 3
    assert parsed[fam + '_bucket{le="+Inf"}'] == 3


# -- compare --slo -----------------------------------------------------------


def _serve_log(path, p50, p99, rps, run_id):
    recs = [
        {"ts": float(i), "rel_s": float(i), "schema_version": 10,
         "kind": "serve", "run_id": run_id, "window_s": 1.0,
         "requests": 10, "completed": 10, "requests_per_s": rps,
         "latency_p50_ms": p50, "latency_p99_ms": p99,
         "ttfb_p99_ms": p99 * 0.8, "availability": 1.0,
         "batch_occupancy": 0.9}
        for i in range(3)
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return str(path)


def test_compare_slo_exit_contract(tmp_path, capsys):
    from tpu_dist.obs import __main__ as obs_main

    base = _serve_log(tmp_path / "b.jsonl", 10.0, 20.0, 100.0, "b")
    worse = _serve_log(tmp_path / "w.jsonl", 30.0, 60.0, 95.0, "w")
    better = _serve_log(tmp_path / "g.jsonl", 5.0, 10.0, 120.0, "g")
    assert obs_main.main(["compare", base, worse, "--slo"]) == 1
    capsys.readouterr()
    assert obs_main.main(["compare", base, better, "--slo"]) == 0
    out = capsys.readouterr().out
    assert "REGRESSED" not in out  # lower latency is never flagged
    # two serve-less logs: the gate compares nothing → broken gate, 2
    t1, t2 = tmp_path / "t1.jsonl", tmp_path / "t2.jsonl"
    for p in (t1, t2):
        p.write_text(json.dumps({
            "ts": 1.0, "rel_s": 1.0, "schema_version": 10,
            "kind": "train_epoch", "epoch": 0, "run_id": "t",
            "images_per_sec": 10.0, "epoch_time": 1.0, "loss": 1.0,
        }) + "\n")
    assert obs_main.main(["compare", str(t1), str(t2), "--slo"]) == 2
    # --slo composes with neither --bench nor --goodput
    assert obs_main.main(["compare", base, worse, "--slo", "--bench"]) == 2
    assert obs_main.main(["compare", base, worse, "--slo", "--goodput"]) == 2


def test_metric_direction_registry():
    from tpu_dist.obs import compare as compare_lib

    assert compare_lib.direction_of("serve_latency_p99_ms") == ("lower", 0.0)
    assert compare_lib.direction_of("serve_requests_per_s") == ("higher", 0.0)
    # suffix defaults for future metrics: latencies lower, rates higher
    assert compare_lib.direction_of("future_thing_ms") == ("lower", 0.0)
    assert compare_lib.direction_of("future_rate_per_s") == ("higher", 0.0)
    with pytest.raises(KeyError):
        compare_lib.direction_of("mystery_metric")
    # the derived tables agree with the registry — no hand-rolled rows
    for key, direction, slack in (
        compare_lib.REPORT_METRICS + compare_lib.SLO_METRICS
    ):
        assert (direction, slack) == compare_lib.direction_of(key)
    slo_keys = {m[0] for m in compare_lib.SLO_METRICS}
    assert "serve_latency_p99_ms" in slo_keys
    assert "serve_requests_per_s" in slo_keys


# -- schema v10 rendering ----------------------------------------------------


def test_serve_records_render_in_summarize_and_tail(tmp_path):
    from tpu_dist.obs import tail as tail_lib
    from tpu_dist.obs.summarize import format_text, load_records, summarize

    log = _serve_log(tmp_path / "s.jsonl", 10.0, 20.0, 100.0, "r")
    with open(log, "a") as f:
        f.write(json.dumps({
            "ts": 4.0, "rel_s": 4.0, "schema_version": 10, "kind": "serve",
            "run_id": "r", "event": "retrace", "bucket": 4, "n_real": 3,
        }) + "\n")
        f.write(json.dumps({
            "ts": 5.0, "rel_s": 5.0, "schema_version": 10, "kind": "alert",
            "run_id": "r", "rule": "slo_p99_high",
            "metric": "serve.latency_p99_ms", "value": 600.0,
            "threshold": 500.0, "op": ">", "sustained": 2,
        }) + "\n")
    records, bad = load_records(log)
    report = summarize(records, bad)
    assert len(report["serve_windows"]) == 3
    assert report["serve_events"] == [
        {"event": "retrace", "bucket": 4, "n_real": 3}
    ]
    assert report["skipped_kinds"] == {}  # serve is a KNOWN kind
    text = format_text(report)
    assert "serving SLO windows" in text
    assert "RETRACE on a bucket-4 batch" in text
    state = tail_lib.TailState()
    state.add(records)
    frame = state.render()
    assert "serve: 100.0 req/s" in frame
    assert "serve RETRACE" in frame
    # the offline serve report CLI engine over the same records
    rep = slo_lib.serve_report(records)
    assert rep["n_windows"] == 3 and len(rep["alerts"]) == 1
    out = slo_lib.format_report_text(rep)
    assert "SLO ALERT slo_p99_high" in out


def test_serve_record_schema_v10_stamp(tmp_path):
    from tpu_dist.metrics.history import SCHEMA_VERSION, MetricsHistory

    assert SCHEMA_VERSION == 15  # v15: causal decision tracing (ISSUE 19)
    path = str(tmp_path / "h.jsonl")
    with MetricsHistory(path, run_id="s10") as h:
        h.log("serve", window_s=1.0, completed=4, latency_p50_ms=3.0)
    rec = json.loads(open(path).read())
    assert rec["schema_version"] == 15 and rec["kind"] == "serve"


def test_serve_cli_report(tmp_path, capsys):
    from tpu_dist.serve import __main__ as serve_main

    log = _serve_log(tmp_path / "s.jsonl", 10.0, 20.0, 100.0, "r")
    assert serve_main.main(["report", log]) == 0
    assert "serve report — 3 window(s)" in capsys.readouterr().out
    empty = tmp_path / "empty.jsonl"
    empty.write_text(json.dumps({"kind": "train_epoch", "epoch": 0}) + "\n")
    assert serve_main.main(["report", str(empty)]) == 1
    assert serve_main.main(["report", str(tmp_path / "nope.jsonl")]) == 2


# -- TD114 -------------------------------------------------------------------


def test_td114_registry_and_audit_all_wiring():
    import inspect

    from tpu_dist.analysis import jaxpr_audit
    from tpu_dist.analysis.rules import RULES

    assert RULES["TD114"].name == "serving-slo-not-noop"
    assert "serving_slo_noop_violations" in inspect.getsource(
        jaxpr_audit.audit_all
    )


def test_td114_gate_serving_slo_is_noop():
    from tpu_dist.analysis.jaxpr_audit import serving_slo_noop_violations

    assert serving_slo_noop_violations() == []


# -- e2e ---------------------------------------------------------------------


@pytest.mark.slow
def test_serve_drill_e2e(tmp_path):
    from tpu_dist.serve.drill import run_drill

    summary = run_drill(str(tmp_path / "drill"))
    assert summary["retraces_post_warmup"] == 0
    assert summary["compare_slo"] == {
        "regression_rc": 1, "improvement_rc": 0,
    }
    assert any(kind == "zero1_flat" for _, kind in summary["remapped"])


@pytest.mark.slow
def test_bench_serve_emits_fingerprinted_record():
    # in-process: ``bench.py`` itself refuses to measure without a chip
    import bench

    rec = bench.run_serve(
        bench.CONFIGS["resnet18_cifar100"], 24, max_batch=4, tiny=True
    )
    for field in ("requests_per_s", "latency_p50_ms", "latency_p99_ms",
                  "batch_occupancy"):
        assert isinstance(rec[field], (int, float)), field
    assert rec["retraces"] == 0
    # the PR 7 capture fingerprint rides along → stale re-emissions of a
    # serving number are auto-flagged by obs compare --bench
    assert rec["capture"]["bench_run_id"]
    assert rec["unit"] == "requests/sec"
