"""The HBM observability layer (ISSUE 14, ``tpu_dist/obs/memory.py``):
static per-leaf ledger arithmetic (sharded extents included), the
census/allocator reconciliation identity on a real CPU fit, the
RESOURCE_EXHAUSTED parser matrix, pre-flight feasibility units and the
trainer's refuse path, the peak-HBM compare gate, the `obs memory` CLI,
OOM postmortem verdicts, the TD115 noop gate, and the schema-v11 pins."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.obs import costmodel
from tpu_dist.obs import memory as memory_lib

# -- static ledger: per-leaf byte arithmetic --------------------------------


def test_static_ledger_matches_hand_byte_arithmetic():
    params = {
        "w": jnp.ones((4, 8), jnp.float32),      # 128 B
        "b": jnp.ones((8,), jnp.bfloat16),       # 16 B
    }
    led = memory_lib.static_ledger(params=params, opt_state=None)
    sec = led["sections"]["params"]
    assert sec["bytes_total"] == 4 * 8 * 4 + 8 * 2 == 144
    assert sec["bytes_per_device"] == 144  # replicated: per-device == total
    assert sec["n_leaves"] == 2 and sec["sharded_leaves"] == 0
    assert led["sections"]["opt_state"]["bytes_total"] == 0
    assert led["bytes_per_device"] == 144 and led["n_leaves"] == 2
    # top leaves sorted by size, carrying shape/dtype for the report
    assert sec["top"][0]["bytes_per_device"] == 128
    assert sec["top"][0]["shape"] == [4, 8]


def test_static_ledger_counts_zero1_shards_at_sharded_extent():
    """A ZeRO-1 flat momentum vector laid P('data') over the 8-device
    mesh must count ceil(L/8) elements per chip, not L — the whole point
    of weight-update sharding (arXiv:2004.13336)."""
    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.comm.quantize import padded_len
    from tpu_dist.train.step import init_sharded_opt_state

    mesh = mesh_lib.data_parallel_mesh()
    n = int(mesh.devices.size)
    if n < 2:
        pytest.skip("needs the emulated multi-device mesh")
    params = {"w": jnp.ones((13, 7), jnp.float32), "b": jnp.ones((5,))}
    L = 13 * 7 + 5
    opt = init_sharded_opt_state(params, mesh)
    led = memory_lib.static_ledger(opt_state=opt)
    sec = led["sections"]["opt_state"]
    P_len = padded_len(L, n)
    assert sec["bytes_total"] == P_len * 4
    assert sec["bytes_per_device"] == P_len // n * 4
    assert sec["sharded_leaves"] == 1


def test_static_ledger_accepts_shape_dtype_structs():
    # the trainer's batch row is a ShapeDtypeStruct (no real arrays at
    # construction); the ledger must price it from metadata alone
    led = memory_lib.static_ledger(batch={
        "images": jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.float32),
        "labels": jax.ShapeDtypeStruct((8,), jnp.int32),
    })
    assert led["bytes_per_device"] == 8 * 32 * 32 * 3 * 4 + 8 * 4


# -- census + reconciliation -------------------------------------------------


def test_reconciliation_identity_exact_by_construction():
    keep = jnp.ones((64, 64))  # held alive through the census
    census = memory_lib.live_census()
    assert census["n_arrays"] >= 1
    assert census["bytes_device0"] >= keep.nbytes
    # CPU backend: no allocator stats -> the census is the authority
    rec = memory_lib.reconcile(census, costmodel.device_memory_stats())
    assert (
        rec["attributed_bytes"] + rec["unattributed_bytes"]
        == rec["bytes_in_use"]
    )
    # a real allocator: unattributed is DEFINED as the difference (the
    # workspace/fragmentation gauge), so the identity is exact even when
    # the allocator holds more -- or less (donated buffers) -- than the
    # census can name
    for in_use in (rec["attributed_bytes"] + 4096,
                   max(rec["attributed_bytes"] - 512, 0)):
        r2 = memory_lib.reconcile(census, {"bytes_in_use": in_use})
        assert r2["source"] == "allocator"
        assert (
            r2["attributed_bytes"] + r2["unattributed_bytes"]
            == r2["bytes_in_use"] == in_use
        )
    del keep


def test_ledger_record_and_gauges(monkeypatch):
    from tpu_dist.obs import counters

    counters.reset()
    led = memory_lib.static_ledger(params={"w": jnp.ones((16,))})
    rec = memory_lib.ledger(
        static=led, xla={"argument_bytes": 10, "output_bytes": 4,
                         "temp_bytes": 2, "generated_code_bytes": 1,
                         "peak_bytes": 17},
    )
    memory_lib.publish_ledger(rec)
    snap = counters.snapshot()
    assert snap["mem.static_bytes_per_device"] == 64
    assert snap["mem.xla_peak_bytes"] == 17
    assert snap["mem.attributed_bytes"] == rec["reconciliation"][
        "attributed_bytes"
    ]
    assert memory_lib.record_peak_hbm(rec) == 17  # xla beats census on CPU
    assert "static" in memory_lib.summary_line(rec)
    counters.reset()


# -- per-device allocator stats (the costmodel satellite fix) ---------------


class _FakeDev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_device_memory_stats_reports_worst_chip_and_skew(monkeypatch):
    """The device-0-only read hid a hot chip behind a cool device 0 —
    the scalar keys must now be the MAX across local devices, with
    min/skew gauges making the imbalance visible."""
    devs = [
        _FakeDev({"bytes_in_use": 100, "peak_bytes_in_use": 150,
                  "bytes_limit": 1000}),
        _FakeDev({"bytes_in_use": 900, "peak_bytes_in_use": 950,
                  "bytes_limit": 1000}),
        _FakeDev(None),  # a device without stats is skipped, not fatal
    ]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    out = costmodel.device_memory_stats()
    assert out["bytes_in_use"] == 900          # the worst chip, not dev 0
    assert out["bytes_in_use_min"] == 100
    assert out["bytes_in_use_skew"] == 800     # the imbalance gauge
    assert out["peak_bytes_in_use"] == 950
    assert out["mem_devices_reporting"] == 2


def test_device_memory_stats_peak_adds_the_reserved_temporaries(monkeypatch):
    """On the TPU runtime ``peak_bytes_in_use`` counts buffers only and the
    executables' temporaries sit in ``peak_bytes_reserved`` (ViT-B/16: 2.9 GB
    beside 9.3 GB): the chip's peak is the worst chip's SUM, and the
    epoch-grain headroom gauge is reckoned from it."""
    from tpu_dist.obs import counters
    from tpu_dist.train.trainer import Trainer

    devs = [
        _FakeDev({"bytes_in_use": 100, "peak_bytes_in_use": 300,
                  "peak_bytes_reserved": 100, "bytes_limit": 1000}),
        _FakeDev({"bytes_in_use": 90, "peak_bytes_in_use": 200,
                  "peak_bytes_reserved": 700, "bytes_limit": 1000}),
    ]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    out = costmodel.device_memory_stats()
    assert out["peak_bytes_in_use"] == 300 and out["peak_bytes_reserved"] == 700
    assert out["peak_bytes"] == 900  # one chip's sum, not the sum of two maxima
    counters.reset()
    Trainer._publish_memory_gauges(None)
    snap = counters.snapshot()
    assert snap["mem.peak_bytes_reserved"] == 700 and snap["mem.peak_bytes"] == 900
    assert snap["mem.headroom_frac"] == pytest.approx(0.1)
    assert memory_lib.record_peak_hbm({"allocator": out}) == 900
    counters.reset()
    # a runtime that reports no reserved bytes: the peak is the allocator's
    monkeypatch.setattr(jax, "local_devices", lambda: [_FakeDev(
        {"bytes_in_use": 100, "peak_bytes_in_use": 150, "bytes_limit": 1000})])
    assert costmodel.device_memory_stats()["peak_bytes"] == 150


def test_device_memory_stats_none_on_statless_backend(monkeypatch):
    monkeypatch.setattr(
        jax, "local_devices", lambda: [_FakeDev(None), _FakeDev({})]
    )
    assert costmodel.device_memory_stats() is None


def test_chip_hbm_budget_table():
    gib = 1024 ** 3
    assert costmodel.chip_hbm_bytes("TPU v5e") == 16 * gib
    assert costmodel.chip_hbm_bytes("TPU v5p") == 95 * gib
    assert costmodel.chip_hbm_bytes("TPU v5p chip") is None  # exact kinds only
    assert costmodel.chip_hbm_bytes("TPU v4") == 32 * gib
    assert costmodel.chip_hbm_bytes("cpu") is None  # never a guess


# -- RESOURCE_EXHAUSTED parser matrix ---------------------------------------

_GPU_OOM = """RESOURCE_EXHAUSTED: Out of memory while trying to allocate 2684354560 bytes.
BufferAssignment OOM Debugging.
Largest program allocations in hbm:
  1. Size: 2.50G
     Operator: op_name="jit(train_step)/dot_general"
     Shape: f32[8192,81920]
  2. Size: 640.0M
     XLA Label: fusion
     Shape: bf16[320,1024,1024]
"""

_TPU_OOM = (
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
    "Ran out of memory in memory space hbm. Used 15.90G of 15.48G hbm. "
    "Exceeded hbm capacity by 430.5M. Total hbm usage >= 16.43G:\n"
    "    reserved        530.00M\n    program          15.90G\n"
)


def test_parse_oom_gpu_shape_with_buffer_table():
    r = memory_lib.parse_resource_exhausted(_GPU_OOM)
    assert r["requested_bytes"] == 2684354560
    assert [b["size_bytes"] for b in r["buffers"]] == [
        int(2.5 * 1024 ** 3), int(640.0 * 1024 ** 2)
    ]
    assert r["buffers"][0]["op"] == "jit(train_step)/dot_general"
    assert r["buffers"][0]["shape"] == "f32[8192,81920]"
    assert r["buffers"][1]["op"] == "fusion"
    assert r["buffers_bytes"] == sum(b["size_bytes"] for b in r["buffers"])
    assert "RESOURCE_EXHAUSTED" in r["headline"]


def test_parse_oom_tpu_used_of_capacity_shape():
    r = memory_lib.parse_resource_exhausted(_TPU_OOM)
    assert r["used_bytes"] == int(15.90 * 1024 ** 3)
    assert r["limit_bytes"] == int(15.48 * 1024 ** 3)
    assert r["excess_bytes"] == int(430.5 * 1024 ** 2)
    line = memory_lib.oom_summary_line(r)
    assert "used" in line and "15.9GiB" in line


def test_parse_oom_truncated_text_still_yields_report():
    # the flight ring caps fatal messages at ~200 chars: the table is
    # gone but the headline + requested size survive
    r = memory_lib.parse_resource_exhausted(_GPU_OOM[:90])
    assert r is not None
    assert r["requested_bytes"] == 2684354560
    assert "buffers" not in r


def test_parse_oom_garbage_and_foreign_errors_return_none():
    assert memory_lib.parse_resource_exhausted("") is None
    assert memory_lib.parse_resource_exhausted("hello world") is None
    assert memory_lib.parse_resource_exhausted(
        "ValueError: shapes (3,) and (4,) not aligned"
    ) is None


# -- pre-flight feasibility --------------------------------------------------


def test_feasibility_headroom_units():
    gib = 1024 ** 3
    f = memory_lib.feasibility(10 * gib, 16 * gib, headroom=0.5)
    assert not f["fits"] and f["allowed_bytes"] == 8 * gib
    assert f["utilization"] == pytest.approx(10 / 16, abs=1e-4)
    assert memory_lib.feasibility(10 * gib, 16 * gib, headroom=0.9)["fits"]
    with pytest.raises(ValueError):
        memory_lib.feasibility(1, 0)
    with pytest.raises(ValueError):
        memory_lib.feasibility(1, 100, headroom=0.0)


def test_preflight_check_actions():
    # refuse: the typed error, before any compile
    with pytest.raises(memory_lib.InfeasibleMemoryError, match="exceeds"):
        memory_lib.preflight_check(
            2048, budget_bytes=1024, action="refuse"
        )
    # warn: report returned, caller prints
    rep = memory_lib.preflight_check(2048, budget_bytes=1024, action="warn")
    assert rep is not None and not rep["fits"]
    # off / unknown chip without an override: no lint, never a guess
    assert memory_lib.preflight_check(
        2048, budget_bytes=1024, action="off"
    ) is None
    assert memory_lib.preflight_check(
        2048, action="warn", chip_kind="cpu"
    ) is None
    with pytest.raises(ValueError, match="off|warn|refuse"):
        memory_lib.preflight_check(1, budget_bytes=10, action="bogus")


def _tiny_cfg(**kw):
    from tests.helpers import tiny_resnet
    from tpu_dist.config import TrainConfig
    from tpu_dist.train import trainer as trainer_mod

    trainer_mod.register_model(
        "tiny_memory", lambda num_classes=10: tiny_resnet(num_classes)
    )
    base = dict(
        dataset="synthetic", model="tiny_memory", num_classes=10,
        batch_size=32, epochs=1, steps_per_epoch=2, eval_every=0,
        synthetic_n=64, log_every=1, seed=0,
    )
    base.update(kw)
    return TrainConfig(**base)


def test_trainer_preflight_refuses_infeasible_budget():
    from tpu_dist.train.trainer import Trainer

    with pytest.raises(memory_lib.InfeasibleMemoryError, match="per-chip"):
        Trainer(_tiny_cfg(hbm_budget_bytes=1024, memory_check="refuse"))
    # the same budget under 'warn' constructs (and stamps the gauge)
    t = Trainer(_tiny_cfg(hbm_budget_bytes=1024, memory_check="warn"))
    assert not t._mem_feasibility["fits"]
    assert t._mem_static["bytes_per_device"] > 1024


def test_cpu_fit_logs_memory_record_with_exact_reconciliation(tmp_path):
    """The acceptance drill: a real CPU fit writes ONE schema-v11
    'memory' record whose reconciliation identity holds exactly, whose
    static section prices the params the model actually has, and whose
    mem.* gauges ride the epoch counters."""
    from tpu_dist.train.trainer import Trainer

    log = tmp_path / "run.jsonl"
    t = Trainer(_tiny_cfg(log_file=str(log)))
    t.fit()
    records = [json.loads(l) for l in open(log) if l.strip()]
    mems = [r for r in records if r.get("kind") == "memory"]
    assert len(mems) == 1, [r.get("kind") for r in records]
    m = mems[0]
    assert m["schema_version"] == 15
    rc = m["reconciliation"]
    assert (
        rc["attributed_bytes"] + rc["unattributed_bytes"]
        == rc["bytes_in_use"]
    )
    assert rc["source"] in ("census", "allocator")
    # static section: params priced from the real state
    params_bytes = sum(
        math.prod(l.shape) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(t.state.params)
    )
    assert m["static"]["sections"]["params"]["bytes_total"] == params_bytes
    # the census saw the live state (params at minimum)
    assert m["census"]["bytes_device0"] >= params_bytes
    # the xla waterfall was captured (telemetry armed -> AOT analysis)
    assert m["xla"]["argument_bytes"] > 0
    assert m["xla"]["peak_bytes"] > 0
    # mem.* gauges flowed into the epoch record's counter snapshot
    epoch_rec = next(r for r in records if r.get("kind") == "train_epoch")
    assert epoch_rec["counters"]["mem.static_bytes_per_device"] > 0
    assert epoch_rec["counters"]["mem.xla_peak_bytes"] == m["xla"]["peak_bytes"]
    # summarize folds it + derives the gate scalar
    from tpu_dist.obs import summarize as summ

    report = summ.summarize(records)
    assert report["memory_records"] and report["memory"]
    assert report["memory"]["peak_hbm_bytes"] is not None
    assert "memory ledger:" in summ.format_text(report)


# -- compare gate ------------------------------------------------------------


def _history_with_peak(path, peak):
    recs = [
        {"ts": 1.0, "rel_s": 1.0, "schema_version": 11, "run_id": "r",
         "kind": "train_epoch", "epoch": 0, "epoch_time": 2.0,
         "images_per_sec": 1000.0, "loss": 1.0},
        {"ts": 2.0, "rel_s": 2.0, "schema_version": 11, "run_id": "r",
         "kind": "memory", "xla": {"peak_bytes": peak},
         "reconciliation": {"attributed_bytes": 0,
                            "unattributed_bytes": 0, "bytes_in_use": 0,
                            "source": "census"},
         "census": {"n_arrays": 0, "bytes_device0": 0}},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return str(path)


def test_compare_exits_1_on_peak_hbm_regression_0_on_improvement(tmp_path):
    from tpu_dist.obs.__main__ import main as obs_main

    gib = 1024 ** 3
    base = _history_with_peak(tmp_path / "b.jsonl", 10 * gib)
    worse = _history_with_peak(tmp_path / "c.jsonl", 12 * gib)
    better = _history_with_peak(tmp_path / "d.jsonl", 9 * gib)
    assert obs_main(["compare", base, worse]) == 1   # higher = regression
    assert obs_main(["compare", base, better]) == 0  # lower never flags
    assert obs_main(["compare", base, base]) == 0    # self-compare clean


def test_peak_hbm_direction_registered_and_in_bench_fields():
    from tpu_dist.obs import compare as cmp

    assert cmp.direction_of("peak_hbm_bytes")[0] == "lower"
    assert "peak_hbm_bytes" in {f[0] for f in cmp.BENCH_FIELDS}
    assert "peak_hbm_bytes" in {m[0] for m in cmp.REPORT_METRICS}


# -- alerts ------------------------------------------------------------------


def test_memory_headroom_low_builtin_rule_fires_on_sustained_breach():
    from tpu_dist.obs import alerts as alerts_lib

    assert "memory_headroom_low" in alerts_lib.BUILTIN_RULES
    engine = alerts_lib.AlertEngine(alerts_lib.load_rules("default"))
    fired = []
    for _ in range(2):  # sustain=2
        fired.extend(engine.observe({"mem.headroom_frac": 0.05}))
    assert [f["rule"] for f in fired] == ["memory_headroom_low"]
    # a healthy window clears it; a backend that never publishes the
    # gauge (CPU) never advances the streak
    engine.observe({"mem.headroom_frac": 0.5})
    assert engine.active()["memory_headroom_low"] == 0.0


# -- obs memory CLI ----------------------------------------------------------


def test_obs_memory_cli_report_and_exit_codes(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    log = _history_with_peak(tmp_path / "r.jsonl", 3 * 1024 ** 3)
    assert obs_main(["memory", log]) == 0
    out = capsys.readouterr().out
    assert "peak HBM" in out and "3.0GiB" in out
    # a history with no memory telemetry: exit 1, loud
    empty = tmp_path / "e.jsonl"
    empty.write_text(json.dumps({
        "ts": 1.0, "kind": "train_epoch", "epoch": 0, "schema_version": 11,
    }) + "\n")
    assert obs_main(["memory", str(empty)]) == 1
    assert obs_main(["memory", str(tmp_path / "missing.jsonl")]) == 2


def test_obs_memory_cli_oom_parse(tmp_path, capsys):
    from tpu_dist.obs.__main__ import main as obs_main

    oom = tmp_path / "oom.txt"
    oom.write_text(_GPU_OOM)
    assert obs_main(["memory", "--oom", str(oom)]) == 0
    out = capsys.readouterr().out
    assert "requested 2.5GiB" in out and "dot_general" in out
    garbage = tmp_path / "g.txt"
    garbage.write_text("nothing to see")
    assert obs_main(["memory", "--oom", str(garbage)]) == 1


# -- OOM drill: postmortem verdict -------------------------------------------


def test_induced_oom_yields_postmortem_verdict_oom(tmp_path):
    """The acceptance drill, host-side: a rank dies on
    RESOURCE_EXHAUSTED — its flight ring holds the (truncated) fatal
    slot and the full oom.json landed beside it. The postmortem verdict
    must be 'oom' with the parsed allocation report, and the history
    record must render per-rank through summarize and tail."""
    from tpu_dist.obs import flight as flight_lib
    from tpu_dist.obs import postmortem as postmortem_lib

    crash = tmp_path / "crash"
    crash.mkdir()
    rec = flight_lib.FlightRecorder(
        str(crash / flight_lib.RING_NAME), run_id="oomtest", rank=0
    )
    rec.step(0, 3)

    class XlaRuntimeError(Exception):
        pass

    err = XlaRuntimeError(_TPU_OOM)
    rec.fatal(XlaRuntimeError, err, None)
    rec.close("exit", clean=False)
    report = memory_lib.parse_resource_exhausted(str(err))
    memory_lib.write_oom_report(
        str(crash / memory_lib.OOM_NAME), report,
        snapshot={"static": {"bytes_per_device": 123, "sections": {}}},
    )
    pm, bundle = postmortem_lib.run_postmortem([str(crash)])
    assert bundle is not None
    rank0 = pm["ranks"][0]
    assert rank0["verdict"] == "oom"
    assert rank0["oom"]["oom"]["used_bytes"] == int(15.90 * 1024 ** 3)
    text = postmortem_lib.format_text(pm)
    assert "OOM" in text and "rank 0: OOM" in text
    # the history record carries the per-rank oom map + renders via the
    # shared rank_summary formatter
    hist = postmortem_lib.history_record(pm, bundle)
    assert hist["verdicts"]["0"] == "oom"
    assert "used 15.9GiB" in hist["oom"]["0"]
    assert "OOM" in postmortem_lib.rank_summary(hist, "0")


def test_ring_only_oom_falls_back_to_fatal_slot_parse(tmp_path):
    """No oom.json (lost with the filesystem): the truncated fatal slot
    alone must still classify the verdict as oom."""
    from tpu_dist.obs import flight as flight_lib
    from tpu_dist.obs import postmortem as postmortem_lib

    crash = tmp_path / "crash"
    crash.mkdir()
    rec = flight_lib.FlightRecorder(
        str(crash / flight_lib.RING_NAME), run_id="oomtest", rank=0
    )

    class XlaRuntimeError(Exception):
        pass

    rec.fatal(XlaRuntimeError, XlaRuntimeError(_GPU_OOM), None)
    rec.close("exit", clean=False)
    pm, _ = postmortem_lib.run_postmortem([str(crash)])
    assert pm["ranks"][0]["verdict"] == "oom"
    assert pm["ranks"][0]["oom"]["source"] == "flight_ring"


def test_trainer_oom_teardown_writes_event_and_artifact(tmp_path, monkeypatch):
    """End-to-end: a RESOURCE_EXHAUSTED propagating out of the step loop
    leaves (a) a 'memory' event:oom history record with the parsed
    report + the live ledger snapshot, (b) oom.json beside the flight
    ring, (c) a ring whose postmortem verdict is 'oom'."""
    from tpu_dist.obs import postmortem as postmortem_lib
    from tpu_dist.train import trainer as trainer_mod

    log = tmp_path / "run.jsonl"
    crash = tmp_path / "crash"
    cfg = _tiny_cfg(log_file=str(log), crash_dir=str(crash))
    t = trainer_mod.Trainer(cfg)

    class XlaRuntimeError(Exception):
        pass

    def boom(*a, **kw):
        raise XlaRuntimeError(_TPU_OOM)

    monkeypatch.setattr(t, "train_epoch", boom)
    with pytest.raises(XlaRuntimeError):
        t.fit()
    records = [json.loads(l) for l in open(log) if l.strip()]
    ooms = [
        r for r in records
        if r.get("kind") == "memory" and r.get("event") == "oom"
    ]
    assert len(ooms) == 1
    assert ooms[0]["oom"]["used_bytes"] == int(15.90 * 1024 ** 3)
    assert ooms[0]["ledger"]["static"]["bytes_per_device"] > 0
    # the artifact landed and the postmortem classifies the rank
    assert (crash / memory_lib.OOM_NAME).exists()
    pm, _ = postmortem_lib.run_postmortem([str(crash)])
    assert pm["ranks"][0]["verdict"] == "oom"
    # summarize + tail render the crash
    from tpu_dist.obs import summarize as summ
    from tpu_dist.obs.tail import TailState

    assert "OOM" in summ.format_text(summ.summarize(records))
    ts = TailState()
    ts.add(records)
    assert any("OOM" in e for e in ts.events)


# -- TD115 gate + registry ---------------------------------------------------


def test_td115_registered_beside_the_noop_family():
    from tpu_dist.analysis.rules import RULES

    assert "TD115" in RULES
    assert RULES["TD115"].name == "memory-ledger-not-noop"
    # the whole armed-vs-off family is present
    for rid in ("TD105", "TD106", "TD107", "TD108", "TD109", "TD110",
                "TD111", "TD112", "TD113", "TD114", "TD115"):
        assert rid in RULES


def test_td115_memory_ledger_noop_gate():
    from tpu_dist.analysis.jaxpr_audit import memory_ledger_noop_violations

    assert memory_ledger_noop_violations() == []


# -- schema v11 pins ---------------------------------------------------------


def test_schema_v15_pins_and_future_kind_tolerance():
    from tpu_dist.metrics.history import SCHEMA_VERSION
    from tpu_dist.obs import summarize as summ
    from tpu_dist.obs.postmortem import POSTMORTEM_SCHEMA_VERSION
    from tpu_dist.fleet.scheduler import FLEET_SCHEMA_VERSION

    assert SCHEMA_VERSION == POSTMORTEM_SCHEMA_VERSION == 15
    assert FLEET_SCHEMA_VERSION == 15
    assert summ.SUPPORTED_SCHEMA == 15
    assert "memory" in summ.KNOWN_KINDS
    assert "tenancy" in summ.KNOWN_KINDS  # v14: the co-scheduling ledger
    # a v16 log's unknown kind: skipped WITH a count, never an error
    report = summ.summarize([
        {"kind": "train_epoch", "epoch": 0, "schema_version": 11,
         "ts": 1.0, "rel_s": 1.0, "epoch_time": 1.0,
         "images_per_sec": 10.0, "loss": 1.0},
        {"kind": "mem_hologram", "schema_version": 16, "ts": 2.0},
    ])
    assert report["skipped_kinds"] == {"mem_hologram": 1}
    assert report["newer_schema_records"] == 1
    assert report["totals"]["n_epochs"] == 1


def test_fmt_bytes_units():
    assert memory_lib.fmt_bytes(512) == "512B"
    assert memory_lib.fmt_bytes(1536) == "1.5KiB"
    assert memory_lib.fmt_bytes(3 * 1024 ** 3) == "3.0GiB"
    assert memory_lib.fmt_bytes(None) == "-"
    assert memory_lib.fmt_bytes(-2048) == "-2.0KiB"
