"""Device-side training health (ISSUE 5): in-step norms (--device_metrics
+ TD107), cost/MFU/memory accounting (obs/costmodel), rolling-window
anomaly detection (obs/anomaly), and the run-compare regression gate
(obs/compare + the CLI exit-code contract)."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.obs import counters
from tpu_dist.obs import costmodel
from tpu_dist.obs.anomaly import AnomalyDetector
from tpu_dist.obs.device_stats import compute_device_stats
from tpu_dist.obs.summarize import format_text, summarize


@pytest.fixture(autouse=True)
def _clean_counters():
    counters.reset()
    yield
    counters.reset()


# -- device_stats: the in-step scalars --------------------------------------


def test_compute_device_stats_known_values():
    grads = {"a": jnp.asarray([3.0, 4.0]), "b": jnp.zeros((2, 2))}
    params = {"a": jnp.asarray([1.0, 0.0]), "b": jnp.zeros((2, 2))}
    new = {"a": jnp.asarray([1.0, 0.2]), "b": jnp.zeros((2, 2))}
    s = jax.tree_util.tree_map(float, compute_device_stats(grads, params, new))
    assert s["grad_norm"] == pytest.approx(5.0)
    assert s["param_norm"] == pytest.approx(1.0)
    assert s["update_ratio"] == pytest.approx(0.2)
    assert s["nonfinite_grads"] == 0.0


def test_compute_device_stats_counts_nonfinite_leaves():
    grads = {
        "ok": jnp.ones(3),
        "nan": jnp.asarray([1.0, float("nan")]),
        "inf": jnp.asarray([float("inf")]),
    }
    p = {k: jnp.ones_like(v) for k, v in grads.items()}
    s = compute_device_stats(grads, p, p)
    assert float(s["nonfinite_grads"]) == 2.0  # leaves, not elements
    assert float(s["param_norm"]) > 0.0
    assert float(s["update_ratio"]) == 0.0  # params unchanged


def test_compute_device_stats_empty_tree_is_defined():
    s = compute_device_stats({}, {}, {})
    assert float(s["grad_norm"]) == 0.0
    assert float(s["update_ratio"]) == 0.0


def test_train_step_device_metrics_values_match_host_arithmetic():
    """The fused-in scalars must equal what host numpy computes from the
    actual before/after params — the update_ratio reflects the APPLIED
    update (momentum, wd, lr all included)."""
    from tests.helpers import TinyMLP
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.state import TrainState
    from tpu_dist.train.step import make_train_step

    mesh = mesh_lib.data_parallel_mesh()
    model = TinyMLP()
    params, st = model.init(jax.random.PRNGKey(0))
    opt = SGD(momentum=0.9, weight_decay=1e-4)
    state = jax.device_put(
        TrainState.create(params, st, opt), mesh_lib.replicated(mesh)
    )
    step = make_train_step(
        model.apply, opt, mesh, sync_bn=False,
        compute_dtype=jnp.float32, device_metrics=True, donate=False,
    )
    n = mesh.devices.size
    rng = np.random.default_rng(0)
    images = mesh_lib.shard_batch(
        mesh, rng.normal(size=(8 * n, 2, 2, 3)).astype(np.float32)
    )
    labels = mesh_lib.shard_batch(
        mesh, rng.integers(0, 10, 8 * n).astype(np.int32)
    )
    before = jax.device_get(state.params)
    new_state, metrics = step(state, images, labels, 0.1)
    m = {k: float(v) for k, v in jax.device_get(metrics).items()}
    after = jax.device_get(new_state.params)
    b = np.concatenate([np.ravel(x) for x in jax.tree_util.tree_leaves(before)])
    a = np.concatenate([np.ravel(x) for x in jax.tree_util.tree_leaves(after)])
    assert m["param_norm"] == pytest.approx(np.linalg.norm(b), rel=1e-5)
    assert m["update_ratio"] == pytest.approx(
        np.linalg.norm(a - b) / np.linalg.norm(b), rel=1e-4
    )
    assert m["grad_norm"] > 0.0 and m["nonfinite_grads"] == 0.0
    # the scalars ride the ordinary metrics dict — the standard keys stay
    assert {"loss", "acc1", "acc5"} <= set(m)


def test_train_step_refuses_device_metrics_on_sharded_paths():
    from tests.helpers import TinyMLP
    from tpu_dist.train.optim import SGD
    from tpu_dist.train.step import make_train_step

    mesh = mesh_lib.data_parallel_mesh()
    model = TinyMLP()
    opt = SGD()
    with pytest.raises(ValueError, match="replicated-param"):
        make_train_step(
            model.apply, opt, mesh, sync_bn=False,
            shard_weight_update=True, device_metrics=True,
        )
    tp_mesh = mesh_lib.device_mesh(
        [mesh.devices.size // 2, 2],
        [mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS],
    )
    with pytest.raises(ValueError, match="replicated-param"):
        make_train_step(
            model.apply, opt, tp_mesh, sync_bn=False,
            tp_axis=mesh_lib.MODEL_AXIS, device_metrics=True,
        )


# -- TD107: the zero-cost contract ------------------------------------------


def test_td107_rule_registered():
    from tpu_dist.analysis.rules import RULES

    assert "TD107" in RULES
    assert "device-metrics" in RULES["TD107"].name


def test_td107_noop_gate():
    """Flag off ⇒ byte-identical jaxpr; flag on ⇒ collective and transfer
    inventories unchanged on the pure-DP path (the acceptance criterion)."""
    from tpu_dist.analysis.jaxpr_audit import device_metrics_noop_violations

    assert device_metrics_noop_violations() == []


def test_td107_audit_case_in_registry():
    from tpu_dist.analysis.jaxpr_audit import audit_all, registered_cases

    assert "dp_device_metrics" in registered_cases()
    report, violations = audit_all(names=["dp_device_metrics"])
    assert not violations
    assert report["dp_device_metrics"]["collectives"]


# -- costmodel ---------------------------------------------------------------


class _FakeAnalyzable:
    def __init__(self, ca=None, ma=None, raise_ca=False):
        self._ca, self._ma, self._raise = ca, ma, raise_ca

    def cost_analysis(self):
        if self._raise:
            raise RuntimeError("unimplemented")
        return self._ca

    def memory_analysis(self):
        if self._ma is None:
            raise RuntimeError("unimplemented")
        return self._ma


def test_chip_peak_flops_exact_kind_and_unknown():
    assert costmodel.chip_peak_flops("TPU v4") == pytest.approx(275e12)
    assert costmodel.chip_peak_flops("TPU v5 lite") == pytest.approx(197e12)
    # an unknown v5-something is NOT priced as its nearest prefix (v5p)
    assert costmodel.chip_peak_flops("TPU v5x") is None
    assert costmodel.chip_peak_flops("TPU v4 lite") is None
    assert costmodel.chip_peak_flops("cpu") is None
    assert costmodel.chip_peak_flops("Tesla V100") is None


def test_require_chip_row_refuses_cpu_and_unknown_tpu():
    class Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    row = costmodel.require_chip_row(Dev("tpu", "TPU v5 lite"))
    assert row == {
        "kind": "TPU v5 lite", "peak_flops": 197e12, "hbm_bytes": 16 * 1024 ** 3,
    }
    with pytest.raises(RuntimeError, match="platform='cpu'"):
        costmodel.require_chip_row(Dev("cpu", "cpu"))
    with pytest.raises(RuntimeError, match="has no row"):
        costmodel.require_chip_row(Dev("tpu", "TPU v5x"))


def test_step_cost_scales_trips():
    obj = _FakeAnalyzable(ca={"flops": 100.0, "bytes accessed": 10.0})
    assert costmodel.step_cost(obj, loop_trips=4) == {
        "flops_per_step": 400.0, "bytes_per_step": 40.0,
    }
    # missing/zero/raising all degrade to None, never raise
    assert costmodel.step_cost(_FakeAnalyzable(ca={"flops": 0.0})) == {
        "flops_per_step": None, "bytes_per_step": None,
    }
    assert costmodel.step_cost(_FakeAnalyzable(raise_ca=True)) == {
        "flops_per_step": None, "bytes_per_step": None,
    }


def test_mfu_arithmetic_and_none_paths():
    # 1e12 flops in 0.1 s on 2 chips of 123e12 peak = 10/24.6
    assert costmodel.mfu(1e12, 0.1, 2, peak=123e12) == pytest.approx(
        1e12 / 0.1 / (2 * 123e12), abs=1e-4
    )
    assert costmodel.mfu(None, 0.1, 1, peak=1e12) is None
    assert costmodel.mfu(1e12, 0.0, 1, peak=1e12) is None
    assert costmodel.mfu(1e12, 0.1, 1, peak=None) is None  # unknown chip


def test_memory_analysis_bytes_aliasing_and_unavailable():
    class MA:
        argument_size_in_bytes = 100
        output_size_in_bytes = 50
        temp_size_in_bytes = 30
        generated_code_size_in_bytes = 5
        alias_size_in_bytes = 60

    out = costmodel.memory_analysis_bytes(_FakeAnalyzable(ma=MA()))
    assert out["peak_bytes"] == 100 + 50 + 30 + 5 - 60
    assert costmodel.memory_analysis_bytes(_FakeAnalyzable()) is None


def test_analyze_jitted_reads_real_cost_without_compiling():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((8, 8))
    cost = costmodel.analyze_jitted(f, x)
    assert cost is not None and cost["flops_per_step"] and cost["flops_per_step"] > 0


def test_publish_sets_gauges():
    costmodel.publish({"flops_per_step": 123.0, "bytes_per_step": None})
    snap = counters.snapshot()
    assert snap["device.flops_per_step"] == 123.0
    assert "device.bytes_per_step" not in snap
    costmodel.publish(None)  # no-op, never raises


def test_compile_watcher_counts_events_and_retraces():
    class FakeJit:
        def __init__(self):
            self.size = 0

        def _cache_size(self):
            return self.size

    fj = FakeJit()
    w = costmodel.CompileWatcher(fj)
    assert w.observe() is False  # nothing compiled yet
    fj.size = 1  # first trace: an event, NOT a retrace
    assert w.observe() is False
    assert counters.get("compile.events") == 1
    assert counters.get("compile.retraces") == 0
    assert w.observe() is False  # steady state: no growth, no counts
    fj.size = 3  # mid-run growth: two retraces
    assert w.observe() is True
    assert counters.get("compile.events") == 3
    assert counters.get("compile.retraces") == 2


def test_compile_watcher_degrades_without_cache_api():
    w = costmodel.CompileWatcher(object())  # no _cache_size attribute
    assert w.observe() is False and counters.get("compile.events") == 0


def test_install_compile_listener_idempotent(monkeypatch):
    from jax import monitoring

    calls = []
    costmodel.install_compile_listener()  # installed (here or earlier)
    monkeypatch.setattr(
        monitoring, "register_event_duration_secs_listener", calls.append
    )
    costmodel.install_compile_listener()
    assert calls == []  # the second call registers nothing


# -- anomaly detector --------------------------------------------------------


def test_anomaly_warmup_then_loss_spike_with_cooldown():
    det = AnomalyDetector(window=8, loss_spike=3.0, min_points=3)
    assert det.observe(loss=100.0) == []  # window cold: no median yet
    for i in range(3):
        assert det.observe(epoch=0, step=i, loss=1.0) == []
    f = det.observe(epoch=0, step=3, loss=10.0)
    assert len(f) == 1 and f[0]["anomaly"] == "loss_spike"
    assert f[0]["ratio"] == pytest.approx(10.0 / f[0]["median"], rel=0.01)
    # cooldown: the plateau right after yields no second record...
    assert det.observe(loss=10.0) == []
    # ...and spikes ENTER the window, so the median self-limits: after the
    # cooldown a 10.0 against a window full of 10.0s is not an anomaly
    for _ in range(8):
        det.observe(loss=10.0)
    assert det.observe(loss=10.0) == []


def test_anomaly_grad_norm_explosion_and_nonfinite():
    det = AnomalyDetector(window=6, grad_spike=10.0, min_points=2)
    for _ in range(3):
        det.observe(grad_norm=1.0)
    f = det.observe(epoch=1, step=7, grad_norm=50.0)
    assert [x["anomaly"] for x in f] == ["grad_norm_explosion"]
    f = det.observe(loss=float("nan"), nonfinite=2.0)
    kinds = {x["anomaly"] for x in f}
    assert kinds == {"nonfinite_loss", "nonfinite_grads"}
    # a nonfinite grad_norm must not poison the rolling window
    det.observe(grad_norm=float("inf"))
    assert all(math.isfinite(v) for v in det._gnorms)


def test_anomaly_cooldown_decays_per_observation_not_per_spike():
    """A kind must come OFF cooldown after min_points observations of any
    kind — an isolated later anomaly separated by healthy steps has to
    fire again (the cooldown exists to collapse a plateau into one
    record, not to swallow distinct events)."""
    det = AnomalyDetector(window=8, loss_spike=3.0, min_points=3)
    for _ in range(3):
        det.observe(loss=1.0)
    assert [f["anomaly"] for f in det.observe(loss=10.0)] == ["loss_spike"]
    # healthy steps tick the cooldown down (and wash the spike out of the
    # rolling window)...
    for _ in range(10):
        assert det.observe(loss=1.0) == []
    # ...so a second, distinct spike fires a second finding
    assert [f["anomaly"] for f in det.observe(loss=10.0)] == ["loss_spike"]
    # same contract for the nonfinite stream: nan, recovery, nan again
    det2 = AnomalyDetector(window=8, min_points=2)
    assert len(det2.observe(loss=float("nan"))) == 1
    for _ in range(3):
        det2.observe(loss=1.0)
    assert len(det2.observe(loss=float("nan"))) == 1


def test_anomaly_rejects_degenerate_window():
    with pytest.raises(ValueError):
        AnomalyDetector(window=1)


# -- compare: the regression gate -------------------------------------------


def _epoch_rec(epoch, ips, loss, run_id="r", mfu=None, **extra):
    rec = {
        "kind": "train_epoch", "epoch": epoch, "run_id": run_id,
        "loss": loss, "epoch_time": 2.0, "images_per_sec": ips,
        "step_time_p50": 0.01, "step_time_p95": 0.02,
        "step_time_p99": 0.03, "data_stall_frac": 0.05,
    }
    if mfu is not None:
        rec["mfu"] = mfu
    rec.update(extra)
    return rec


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return str(path)


def test_compare_self_is_zero_regressions(tmp_path):
    from tpu_dist.obs import compare as cmp

    p = _write_jsonl(
        tmp_path / "a.jsonl",
        [_epoch_rec(0, 1000.0, 2.0, mfu=0.3),
         _epoch_rec(1, 1100.0, 1.5, mfu=0.31),
         {"kind": "eval", "epoch": 1, "top1": 55.0}],
    )
    result = cmp.compare_files(p, p)
    assert result["regressions"] == 0 and result["compared"] == 8
    assert "REGRESSED" not in cmp.format_text(result)


def test_compare_flags_regressions_and_respects_direction(tmp_path):
    from tpu_dist.obs import compare as cmp

    base = _write_jsonl(
        tmp_path / "base.jsonl", [_epoch_rec(0, 1000.0, 2.0, mfu=0.30)]
    )
    # throughput down 20%, p95 up 50%, loss up, MFU down beyond slack
    worse = _write_jsonl(
        tmp_path / "cand.jsonl",
        [_epoch_rec(0, 800.0, 2.5, mfu=0.20, step_time_p95=0.03)],
    )
    result = cmp.compare_files(base, worse, threshold=0.05)
    verdicts = {r["metric"]: r["verdict"] for r in result["rows"]}
    assert verdicts["images_per_sec_mean"] == "REGRESSED"
    assert verdicts["step_time_p95_s"] == "REGRESSED"
    assert verdicts["mfu_mean"] == "REGRESSED"
    assert verdicts["step_time_p50_s"] == "ok"
    # better-than-baseline is never flagged
    better = _write_jsonl(
        tmp_path / "better.jsonl", [_epoch_rec(0, 2000.0, 1.0, mfu=0.5)]
    )
    assert cmp.compare_files(base, better)["regressions"] == 0


def test_compare_absolute_slack_quiets_noise_floor(tmp_path):
    from tpu_dist.obs import compare as cmp

    # stall 0.1% vs 0.3%: a 3x relative blowup but inside the 2-point
    # absolute slack — must NOT regress (the quiet-run noise floor)
    base = _write_jsonl(
        tmp_path / "b.jsonl", [_epoch_rec(0, 1000.0, 2.0, data_stall_frac=0.001)]
    )
    cand = _write_jsonl(
        tmp_path / "c.jsonl", [_epoch_rec(0, 1000.0, 2.0, data_stall_frac=0.003)]
    )
    result = cmp.compare_files(base, cand)
    row = next(r for r in result["rows"] if r["metric"] == "data_stall_frac")
    assert row["verdict"] == "ok"


def test_compare_missing_metrics_reported_skipped_not_dropped(tmp_path):
    from tpu_dist.obs import compare as cmp

    base = _write_jsonl(tmp_path / "b.jsonl", [_epoch_rec(0, 1000.0, 2.0)])
    cand = _write_jsonl(tmp_path / "c.jsonl", [_epoch_rec(0, 1000.0, 2.0)])
    result = cmp.compare_files(base, cand)  # no mfu/eval/goodput/capture
    skipped = {r["metric"] for r in result["rows"] if r["verdict"] == "skipped"}
    assert skipped == {"mfu_mean", "final_val_top1", "goodput_frac",
                       "overlap_frac", "collective_frac",
                       "peak_hbm_bytes", "ckpt_s", "preempt_for_serve_s"}
    assert result["skipped"] == 8


def test_compare_bench_mode_matches_by_metric_name(tmp_path):
    from tpu_dist.obs import compare as cmp

    base = _write_jsonl(tmp_path / "b.json", [
        {"metric": "resnet18_train_throughput", "value": 2600.0,
         "sec_per_epoch": 19.2, "step_ms": 97.0, "mfu": 0.32},
        {"metric": "only_in_base", "value": 1.0},
    ])
    cand = _write_jsonl(tmp_path / "c.json", [
        {"metric": "resnet18_train_throughput", "value": 2000.0,
         "sec_per_epoch": 25.0, "step_ms": 126.0, "mfu": 0.25},
    ])
    result = cmp.compare_files(base, cand, bench=True)
    verdicts = {r["metric"]: r["verdict"] for r in result["rows"]}
    assert verdicts["resnet18_train_throughput.value"] == "REGRESSED"
    assert verdicts["resnet18_train_throughput.sec_per_epoch"] == "REGRESSED"
    assert verdicts["only_in_base"] == "skipped"
    # self-compare in bench mode too
    assert cmp.compare_files(base, base, bench=True)["regressions"] == 0


def test_compare_unusable_inputs_raise(tmp_path):
    from tpu_dist.obs import compare as cmp

    empty = _write_jsonl(tmp_path / "empty.jsonl", [])
    good = _write_jsonl(tmp_path / "g.jsonl", [_epoch_rec(0, 1000.0, 2.0)])
    with pytest.raises(ValueError):
        cmp.compare_files(empty, good)
    no_epochs = _write_jsonl(
        tmp_path / "ne.jsonl", [{"kind": "eval", "epoch": 0, "top1": 1.0}]
    )
    with pytest.raises(ValueError):
        cmp.compare_files(no_epochs, good)


def test_compare_cli_exit_code_contract(tmp_path, capsys):
    """Exit 0 on self-compare, 1 on a regression, 2 on a broken gate —
    the CI contract from the acceptance criteria."""
    from tpu_dist.obs.__main__ import main as obs_main

    base = _write_jsonl(
        tmp_path / "b.jsonl",
        [_epoch_rec(0, 1000.0, 2.0), _epoch_rec(1, 1000.0, 1.8)],
    )
    worse = _write_jsonl(
        tmp_path / "w.jsonl",
        [_epoch_rec(0, 700.0, 2.0), _epoch_rec(1, 700.0, 1.8)],
    )
    assert obs_main(["compare", base, base]) == 0
    assert obs_main(["compare", base, worse]) == 1
    # --format json stays machine-readable on both verdicts
    assert obs_main(["compare", base, worse, "--format", "json"]) == 1
    out = capsys.readouterr().out.splitlines()
    result = json.loads("\n".join(out[out.index("{"):]))
    assert result["regressions"] >= 1
    # a generous threshold waves the same diff through
    assert obs_main(["compare", base, worse, "--threshold", "0.5"]) == 0
    assert obs_main(["compare", base, str(tmp_path / "missing.jsonl")]) == 2
    torn = tmp_path / "torn.jsonl"
    torn.write_text('{"kind": "train_ep')  # only a torn line: unusable
    assert obs_main(["compare", base, str(torn)]) == 2


# -- summarize over the new record kinds ------------------------------------


def test_summarize_aggregates_device_stats_and_anomalies():
    records = [
        _epoch_rec(0, 1000.0, 2.0, mfu=0.31),
        {"kind": "device_stats", "epoch": 0, "step": 0,
         "grad_norm": 1.5, "param_norm": 10.0, "update_ratio": 0.002},
        {"kind": "device_stats", "epoch": 0, "step": 2,
         "grad_norm": 9.0, "param_norm": 10.1, "update_ratio": 0.004},
        {"kind": "device_stats", "epoch": 0, "step": 4,
         "grad_norm": 1.2, "param_norm": 10.2, "update_ratio": 0.003},
        {"kind": "anomaly", "epoch": 0, "step": 2,
         "anomaly": "grad_norm_explosion", "value": 9.0, "median": 1.4,
         "ratio": 6.4},
    ]
    report = summarize(records)
    ds = report["epochs"][0]["device_stats"]
    assert ds["samples"] == 3
    assert ds["grad_norm_max"] == 9.0  # the spike, not the last sample
    assert ds["grad_norm_last"] == 1.2
    assert ds["update_ratio_last"] == 0.003
    assert report["epochs"][0]["mfu"] == 0.31
    assert report["totals"]["mfu_mean"] == pytest.approx(0.31)
    assert report["anomalies"] == [{
        "epoch": 0, "step": 2, "anomaly": "grad_norm_explosion",
        "value": 9.0, "median": 1.4, "ratio": 6.4,
    }]
    text = format_text(report)
    assert "grad_norm last 1.2 / max 9" in text
    assert "anomaly: epoch 0 step 2 grad_norm_explosion value 9.0" in text
    assert "mean MFU 0.31" in text


def test_summarize_surfaces_mid_run_retraces():
    records = [
        _epoch_rec(0, 1000.0, 2.0, counters={"compile.events": 1}),
        _epoch_rec(1, 900.0, 1.9,
                   counters={"compile.events": 3, "compile.retraces": 2}),
    ]
    report = summarize(records)
    assert "retraces" not in report["epochs"][0]
    assert report["epochs"][1]["retraces"] == 2
    assert "2 mid-run retrace(s)" in format_text(report)


# -- trainer wiring ----------------------------------------------------------


def _tiny_cfg(**kw):
    from tests.helpers import tiny_resnet
    from tpu_dist.config import TrainConfig
    from tpu_dist.train import trainer as trainer_mod

    trainer_mod.register_model(
        "tiny_dev_health", lambda num_classes=10: tiny_resnet(num_classes)
    )
    base = dict(
        dataset="synthetic", model="tiny_dev_health", num_classes=10,
        batch_size=32, epochs=1, steps_per_epoch=4, eval_every=0,
        synthetic_n=128, log_every=2, seed=0,
    )
    base.update(kw)
    return TrainConfig(**base)


@pytest.mark.slow  # ~4 s (several Trainer constructions); CI device-
# health step runs it without the slow filter (ISSUE 7 tier-1 budget)
def test_trainer_refuses_device_metrics_on_excluded_engines(tmp_path):
    from tpu_dist.train.trainer import Trainer

    with pytest.raises(ValueError, match="replicated-param"):
        Trainer(_tiny_cfg(device_metrics=True, shard_weight_update=True))
    with pytest.raises(ValueError, match="per-step metrics fetch"):
        Trainer(_tiny_cfg(device_metrics=True, fused_epoch=True))


def test_trainer_refuses_snapshot_action_without_ckpt_dir():
    from tpu_dist.train.trainer import Trainer

    with pytest.raises(ValueError, match="needs --ckpt_dir"):
        Trainer(_tiny_cfg(anomaly_action="snapshot"))
    with pytest.raises(ValueError, match="off|warn|snapshot"):
        Trainer(_tiny_cfg(anomaly_action="bogus"))


def test_observe_health_records_warns_and_snapshots(tmp_path):
    """The full action path, driven with canned metrics: device_stats +
    anomaly history records, per-step TensorBoard scalars, and the
    snapshot action writing an exact mid-epoch checkpoint stamped with
    the anomaly kind."""
    import tpu_dist.ckpt as ckpt_lib
    from tpu_dist.metrics.history import MetricsHistory
    from tpu_dist.train.trainer import Trainer

    ckpt_dir = str(tmp_path / "ckpt")
    t = Trainer(_tiny_cfg(
        anomaly_action="snapshot", anomaly_window=4, anomaly_loss_spike=2.0,
        ckpt_dir=ckpt_dir, device_metrics=True,
    ))
    scalars = []

    class FakeTB:
        def add_scalar(self, tag, value, step):
            scalars.append((tag, value, step))

    t._tb = FakeTB()
    log = tmp_path / "h.jsonl"
    with MetricsHistory(str(log), run_id="t") as h:
        t._history = h
        nb = 10
        for step, loss in enumerate([1.0, 1.1, 0.9, 1.0]):
            t._observe_health(0, step, nb, {
                "loss": loss, "grad_norm": 1.0, "param_norm": 5.0,
                "update_ratio": 1e-3, "nonfinite_grads": 0.0,
            })
        t._observe_health(0, 4, nb, {
            "loss": 8.0, "grad_norm": 1.1, "param_norm": 5.0,
            "update_ratio": 1e-3, "nonfinite_grads": 0.0,
        })
    t._history = None
    recs = [json.loads(l) for l in open(log)]
    kinds = [r["kind"] for r in recs]
    assert kinds.count("device_stats") == 5
    anom = [r for r in recs if r["kind"] == "anomaly"]
    assert len(anom) == 1 and anom[0]["anomaly"] == "loss_spike"
    assert anom[0]["step"] == 4 and anom[0]["ratio"] == pytest.approx(8.0)
    # snapshot: exact mid-epoch checkpoint stamped with the finding,
    # written OFF the ckpt_{N} namespace so later saves never clobber it
    path = os.path.join(ckpt_dir, "anomaly_0_s5.npz")
    assert os.path.exists(path)
    assert not os.path.exists(os.path.join(ckpt_dir, "ckpt_0.npz"))
    meta = ckpt_lib.read_meta(path)
    assert meta["anomaly"] == "loss_spike" and meta["mid_epoch_step"] == 5
    assert counters.get("anomaly.findings") == 1
    assert counters.get("anomaly.snapshots") == 1
    # per-step TB scalars at the global step, loss + the device norms
    tags = {s[0] for s in scalars}
    assert {"step/loss", "step/grad_norm", "step/update_ratio"} <= tags
    assert (("step/loss", 8.0, 4)) in scalars


def test_observe_health_epoch_grain_snapshot_for_fused_path(tmp_path):
    """The fused path observes at step=None (epoch-mean loss only); the
    snapshot action must still write a checkpoint — a clean end-of-epoch
    one, stamped with the finding, NOT a silent degrade to warn."""
    import tpu_dist.ckpt as ckpt_lib
    from tpu_dist.train.trainer import Trainer

    ckpt_dir = str(tmp_path / "ckpt")
    t = Trainer(_tiny_cfg(
        anomaly_action="snapshot", anomaly_window=4, anomaly_loss_spike=2.0,
        ckpt_dir=ckpt_dir,
    ))
    for epoch, loss in enumerate([1.0, 1.1, 0.9, 1.0]):
        t._observe_health(epoch, None, 0, {"loss": loss})
    t._observe_health(4, None, 0, {"loss": 9.0})
    path = os.path.join(ckpt_dir, "anomaly_4.npz")
    assert os.path.exists(path)
    meta = ckpt_lib.read_meta(path)
    assert meta["anomaly"] == "loss_spike"
    assert "mid_epoch_step" not in meta  # clean epoch-boundary checkpoint
    assert counters.get("anomaly.snapshots") == 1


@pytest.mark.slow  # two short fits (~30 s): CI observability step + full suite
def test_e2e_device_metrics_run_logs_and_fetch_parity(tmp_path, monkeypatch):
    """Acceptance: a --device_metrics run writes device_stats records the
    summarize CLI reports, publishes the cost gauges, and issues EXACTLY
    as many per-step fetches as a metrics-off run (the fetch-count half
    of TD107)."""
    from tpu_dist.train import trainer as trainer_mod

    calls = []
    real_fetch = trainer_mod._fetch_metrics
    monkeypatch.setattr(
        trainer_mod, "_fetch_metrics",
        lambda m: (calls.append(1), real_fetch(m))[1],
    )
    counts = {}
    log = str(tmp_path / "dm.jsonl")
    for dm in (False, True):
        calls.clear()
        cfg = _tiny_cfg(
            device_metrics=dm, log_file=log if dm else None, epochs=1,
            steps_per_epoch=4, log_every=2,
        )
        trainer_mod.Trainer(cfg).fit()
        counts[dm] = len(calls)
    assert counts[False] == counts[True], counts
    recs = [json.loads(l) for l in open(log)]
    ds = [r for r in recs if r["kind"] == "device_stats"]
    assert ds and all(
        {"grad_norm", "param_norm", "update_ratio", "nonfinite_grads"}
        <= set(r) for r in ds
    )
    te = [r for r in recs if r["kind"] == "train_epoch"]
    assert te and te[0]["counters"]["device.flops_per_step"] > 0
    assert te[0]["counters"]["compile.events"] >= 1
    assert "compile.retraces" not in te[0]["counters"]  # clean run
    # the summarize CLI surfaces the device block
    from tpu_dist.obs.__main__ import main as obs_main

    assert obs_main(["summarize", log]) == 0


@pytest.mark.slow  # full fit (~15 s)
def test_e2e_mfu_reported_when_chip_peak_known(tmp_path, monkeypatch):
    """With a (stubbed) known chip peak, the epoch summary, the history
    record, and the compare scalars all carry MFU."""
    from tpu_dist.train import trainer as trainer_mod

    # a deliberately tiny stub peak: the tiny model's real flop count over
    # a CPU-emulation step time must still round to a nonzero "MFU"
    monkeypatch.setattr(costmodel, "chip_peak_flops", lambda kind=None: 1e6)
    log = str(tmp_path / "mfu.jsonl")
    cfg = _tiny_cfg(log_file=log, epochs=1, steps_per_epoch=4)
    result = trainer_mod.Trainer(cfg).fit()
    assert 0.0 < result["mfu"]
    te = [json.loads(l) for l in open(log) if '"train_epoch"' in l]
    assert te[0]["mfu"] == result["mfu"]
    from tpu_dist.obs.compare import load_history_scalars

    assert load_history_scalars(log)["mfu_mean"] == result["mfu"]


def test_fused_steps_per_epoch():
    from tpu_dist.train.epoch import fused_steps_per_epoch

    assert fused_steps_per_epoch(50_000, 256) == 195
    assert fused_steps_per_epoch(100, 256) == 1  # never zero trips
