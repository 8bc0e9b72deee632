"""Layer 3 (`shardlint`) tested: the optimized-HLO collective parser
(synthetic modules, version-drift robustness), the TD116
compiled-vs-predicted agreement on the audit matrix (exact on the audit
MLP), the TD117 injected-reshard catch, the quantized-mode ratio pins at
the HLO level, the shard_report schema round-trip, the rules-registry /
docs table parity, and the compare-gate registration of
``hlo_wire_bytes_per_step``."""

import json
import os
import re

import pytest

from tpu_dist.analysis import shardlint
from tpu_dist.analysis.rules import RULES
from tpu_dist.analysis.shardlint import (
    HLOCollective,
    HLOParseError,
    ShardReportError,
    parse_hlo_collectives,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the parser on synthetic HLO ---------------------------------------------


_SYNTHETIC = """\
HloModule synthetic, entry_computation_layout={(f32[128]{0})->f32[128]{0}}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

%loop_body (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %p = (s32[], f32[64]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[64]) %p), index=0
  %x = f32[64] get-tuple-element((s32[], f32[64]) %p), index=1
  %perm = f32[64] collective-permute(f32[64] %x), channel_id=5, source_target_pairs={{0,1},{1,0}}
  ROOT %t = (s32[], f32[64]) tuple(s32[] %i, f32[64] %perm)
}

%loop_cond (p: (s32[], f32[64])) -> pred[] {
  %p = (s32[], f32[64]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[64]) %p), index=0
  ROOT %lt = pred[] compare(s32[] %i, s32[] %i), direction=LT
}

ENTRY %main (x: f32[128]) -> f32[128] {
  %x = f32[128] parameter(0)
  %ar = f32[128] all-reduce(f32[128] %x), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(step)/psum"}
  %rs = f32[32] reduce-scatter(f32[128] %ar), channel_id=2, replica_groups=[1,4]<=[4], dimensions={0}, to_apply=%add
  %ag = f32[128] all-gather(f32[32] %rs), channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}
  %a2a = (s8[16]{0}, s8[16]{0}) all-to-all(s8[16]{0} %x, s8[16]{0} %x), replica_groups={{0,1}}
  %w = (s32[], f32[64]) while((s32[], f32[64]) %x), condition=%loop_cond, body=%loop_body
  ROOT %out = f32[128] copy(f32[128] %ag)
}
"""


def test_parser_synthetic_module():
    ops = parse_hlo_collectives(_SYNTHETIC, loop_trips=3)
    by_kind = {op.kind: op for op in ops}
    assert sorted(by_kind) == [
        "all-gather", "all-reduce", "all-to-all",
        "collective-permute", "reduce-scatter",
    ]
    ar = by_kind["all-reduce"]
    assert (ar.elems, ar.wire_bytes) == (128, 128 * 4 * 2)  # 2 ring legs
    assert ar.replica_groups == "{{0,1,2,3}}"
    assert ar.channel_id == 1
    assert ar.op_name == "jit(step)/psum"
    # reduce-scatter costed on its operand; iota-format groups captured
    rs = by_kind["reduce-scatter"]
    assert (rs.elems, rs.wire_bytes) == (128, 512)
    assert rs.replica_groups == "[1,4]<=[4]"
    # all-gather costed on its gathered OUTPUT
    ag = by_kind["all-gather"]
    assert (ag.elems, ag.wire_bytes) == (128, 512)
    # variadic tuple all-to-all: every int8 operand counted, int bytes
    a2a = by_kind["all-to-all"]
    assert (a2a.elems, a2a.wire_bytes, a2a.int_bytes) == (32, 32, 32)
    # the while-resident permute is multiplied by the declared trip count
    cp = by_kind["collective-permute"]
    assert cp.in_loop and cp.loop_trips == 3
    assert (cp.elems, cp.wire_bytes) == (64 * 3, 64 * 4 * 3)
    assert cp.replica_groups == "{{0,1},{1,0}}"


def test_parser_async_start_done_pairs():
    text = (
        "HloModule async\n\n"
        "ENTRY %main (x: f32[32]) -> f32[128] {\n"
        "  %x = f32[32] parameter(0)\n"
        "  %s = (f32[32]{0}, f32[128]{0}) all-gather-start(f32[32] %x), "
        "channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}\n"
        "  ROOT %d = f32[128] all-gather-done((f32[32]{0}, f32[128]{0}) %s)\n"
        "}\n"
    )
    ops = parse_hlo_collectives(text)
    # -start folds into its base kind, costed on the true output; -done
    # is skipped (counting both would double the wire)
    assert len(ops) == 1
    assert ops[0].kind == "all-gather"
    assert (ops[0].elems, ops[0].wire_bytes) == (128, 512)


# -- robustness: drifted/truncated/foreign inputs never crash audit ----------


def test_parser_typed_errors():
    with pytest.raises(HLOParseError, match="empty"):
        parse_hlo_collectives("")
    with pytest.raises(HLOParseError, match="StableHLO/MLIR"):
        parse_hlo_collectives('module @jit_f {\n  stablehlo.add\n}\n')
    with pytest.raises(HLOParseError, match="not HLO"):
        parse_hlo_collectives("definitely not a module dump")
    with pytest.raises(HLOParseError, match="truncated"):
        parse_hlo_collectives(
            "HloModule m\n\nENTRY %main (a: f32[2]) -> f32[2] {\n"
            "  %a = f32[2] parameter(0)\n"  # no closing brace
        )


def test_parser_version_drift_degrades_not_crashes():
    # a renamed future opcode is simply not a collective; a missing
    # replica_groups parses to None instead of crashing
    text = (
        "HloModule m\n\n"
        "ENTRY %main (a: f32[8]) -> f32[8] {\n"
        "  %a = f32[8] parameter(0)\n"
        "  %r = f32[8] all-reduce(f32[8] %a), channel_id=1, to_apply=%add\n"
        "  %z = f32[8] fancy-new-reduce(f32[8] %r), replica_groups={{0,1}}\n"
        "}\n"
    )
    ops = parse_hlo_collectives(text)
    assert len(ops) == 1
    assert ops[0].replica_groups is None
    assert ops[0].wire_bytes == 8 * 4 * 2


def test_collective_free_jit_yields_empty_inventory():
    import jax
    import jax.numpy as jnp

    jitted = jax.jit(lambda x: x * 2.0 + 1.0)
    text = jitted.lower(jnp.ones((16,))).compile().as_text()
    assert parse_hlo_collectives(text) == []


def test_shard_all_skips_broken_family_with_count(monkeypatch):
    def broken(mesh):
        raise RuntimeError("builder exploded")

    monkeypatch.setitem(
        shardlint._FAMILIES,
        "broken",
        shardlint.ConfigFamily("broken", broken),
    )
    report, violations = shardlint.shard_all(names=["dp_sgd", "broken"])
    assert "dp_sgd" in report["families"]
    assert report["skips"]["broken"].startswith("RuntimeError")
    assert report["counts"]["skipped"] == 1
    assert violations == []


# -- TD116 on the audit matrix (exact on the audit MLP) ----------------------


@pytest.fixture(scope="module")
def dp_matrix():
    names = [
        "dp_sgd", "dp_wire_bf16", "dp_int8", "dp_int8_ef",
        "zero1_sgd", "zero1_int8",
    ]
    report, violations = shardlint.shard_all(names=names)
    assert report["skips"] == {}
    return report, violations


def test_td116_matrix_clean_and_exact(dp_matrix):
    report, violations = dp_matrix
    assert violations == [], [v.format_text() for v in violations]
    for name, fam in report["families"].items():
        v = fam["verdict"]
        assert v["agree"], (name, v)
        # EXACT agreement on the audit MLP: the two accountings price the
        # same elements, and integer legs the same bytes
        assert v["hlo"]["elems"] == v["predicted"]["elems"], name
        assert v["hlo"]["int_bytes"] == v["predicted"]["int_bytes"], name
    # absolute pins for the flagship cases (480-param MLP, 8-dev mesh):
    # f32 allreduce family moves 480*4*2 grad + 8 loss + 16 count bytes
    assert report["families"]["dp_sgd"]["hlo"]["bytes"] == 3864
    # ZeRO-1: RS(480)+AG(480) moves exactly what the allreduce moved
    assert report["families"]["zero1_sgd"]["hlo"]["bytes"] == 3864
    # the quantized two-stage reduce: int8 payload both legs + scales
    assert report["families"]["dp_int8"]["hlo"]["bytes"] == 1048


def test_float_wire_regime_detection(dp_matrix):
    report, _ = dp_matrix
    fams = report["families"]
    # f32 wire is native everywhere
    assert fams["dp_sgd"]["hlo"]["float_wire"] == "native"
    # the CPU backend's float-normalization pass widens the bf16 wire to
    # f32 — detected and DECLARED, not silently passed or spuriously
    # flagged (on TPU this comes back "native")
    assert fams["dp_wire_bf16"]["hlo"]["float_wire"] in (
        "native", "widened_to_f32",
    )
    # int8 legs can never be float-normalized: they stay byte-exact
    assert (
        fams["dp_int8"]["verdict"]["hlo"]["int_bytes"]
        == fams["dp_int8"]["verdict"]["predicted"]["int_bytes"]
        > 0
    )


def test_hlo_ratio_pins_quantized_modes(dp_matrix):
    """The TD104 ratio pins hold on the COMPILED artifact: across the
    wire modes {none, bf16, int8, int8_ef} the quantized gradient payload
    stays <= 0.5x the bf16 mode's and <= 0.25x the uncompressed mode's —
    the compiler must not silently widen a quantized leg (it cannot
    float-normalize int8). Equality allowed: the audit MLP's 480 params
    divide every mesh width, so padding is zero."""
    report, _ = dp_matrix
    payload = {
        name: report["families"][name]["hlo"]["wire"]["payload_bytes"]
        for name in ("dp_sgd", "dp_wire_bf16", "dp_int8", "dp_int8_ef")
    }
    assert payload["dp_int8"] <= 0.5 * payload["dp_wire_bf16"]
    assert payload["dp_int8"] <= 0.25 * payload["dp_sgd"]
    assert payload["dp_int8_ef"] <= 0.5 * payload["dp_wire_bf16"]
    assert payload["dp_int8_ef"] <= 0.25 * payload["dp_sgd"]
    # and the quantized payload is genuinely integer on the wire
    assert report["families"]["dp_int8"]["hlo"]["wire"][
        "quantized_payload_bytes"
    ] == payload["dp_int8"]


# -- TD117: the injected unintended reshard ----------------------------------


def test_td117_injected_bad_in_shardings_caught():
    from tpu_dist.comm import mesh as mesh_lib

    m = mesh_lib.data_parallel_mesh()
    inj = shardlint.injected_bad_zero1(m)
    report, violations = shardlint.shard_case(
        "zero1_sgd", m, step_override=inj
    )
    rules = {v.rule for v in violations}
    assert "TD117" in rules, [v.format_text() for v in violations]
    td117 = [v for v in violations if v.rule == "TD117"]
    # the finding names op kind, bytes, and the replica groups involved
    assert any("all-gather" in v.message for v in td117)
    assert any("replica_groups" in v.message or "B" in v.message
               for v in td117)
    assert report["verdict"]["agree"] is False


def test_td117_gspmd_family_kind_gate():
    ops = [
        HLOCollective(
            kind="collective-permute", shape="f32[64]", dtype="f32",
            elems=64, wire_bytes=256, int_bytes=0, float_bytes=256,
            replica_groups="{{0,1}}", channel_id=9, op_name="x",
            source="", computation="main", in_loop=False, loop_trips=1,
        )
    ]
    vs = shardlint.check_expected_kinds(
        "fsdp", ops, ("all-reduce", "all-gather", "reduce-scatter")
    )
    assert [v.rule for v in vs] == ["TD117"]
    assert "collective-permute" in vs[0].message


# -- the model-parallel + gspmd + serve families -----------------------------


def test_extended_families_clean():
    report, violations = shardlint.shard_all(
        names=["fsdp", "tp_vit", "sp_vit", "serve_eval"]
    )
    assert report["skips"] == {}
    assert violations == [], [v.format_text() for v in violations]
    fams = report["families"]
    # GSPMD inserted real collectives for fsdp even though the jaxpr
    # predicts none — the kind gate passed and the bytes are reported
    assert fams["fsdp"]["hlo"]["bytes"] > 0
    assert fams["fsdp"]["verdict"]["skipped_td116"]
    # ring attention: the permutes live INSIDE the ring scan and the
    # loop-trip pricing still matches the jaxpr model exactly
    sp_ops = fams["sp_vit"]["collectives"]
    assert any(
        o["kind"] == "collective-permute" and o["in_loop"] for o in sp_ops
    )
    # the serve forward step carries only the metric reduces
    assert set(fams["serve_eval"]["hlo"]["by_kind"]) == {"all-reduce"}


# -- shard_report.json: schema-pinned round-trip -----------------------------


@pytest.fixture(scope="module")
def dp_report():
    report, _ = shardlint.build_shard_report(names=["dp_sgd"])
    return report


def test_shard_report_roundtrip(tmp_path, dp_report):
    report = dp_report
    path = str(tmp_path / "shard_report.json")
    shardlint.save_shard_report(report, path)
    loaded = shardlint.load_shard_report(path)
    assert loaded["schema"] == shardlint.SCHEMA
    fam = loaded["families"]["dp_sgd"]
    assert fam["hlo"]["bytes"] == report["families"]["dp_sgd"]["hlo"]["bytes"]
    # pricing keys present
    for key in ("collectives", "hbm", "cost", "predicted_step", "verdict"):
        assert key in fam
    # a FOREIGN schema tag is a typed, loud error (a newer
    # shard_report_vN is tolerated instead — the next test)
    bad = dict(loaded, schema="other_report_v1")
    bad_path = str(tmp_path / "bad.json")
    with open(bad_path, "w") as f:
        json.dump(bad, f)
    with pytest.raises(ShardReportError, match="schema"):
        shardlint.load_shard_report(bad_path)
    # a family entry missing pricing keys is equally loud
    broken = json.loads(json.dumps(loaded))
    del broken["families"]["dp_sgd"]["predicted_step"]
    broken_path = str(tmp_path / "broken.json")
    with open(broken_path, "w") as f:
        json.dump(broken, f)
    with pytest.raises(ShardReportError, match="missing"):
        shardlint.load_shard_report(broken_path)


def test_shard_report_newer_schema_tolerated_with_count(tmp_path, dp_report):
    """Forward compat: a newer-versioned report keeps its readable
    families and skips-with-count the ones missing the v1 keys (the
    same-version case above is corruption and raises)."""
    future = json.loads(json.dumps(dp_report))
    future["schema"] = "shard_report_v2"
    future["families"]["v2_only"] = {"note": "no v1 keys at all"}
    path = str(tmp_path / "future_shard.json")
    with open(path, "w") as f:
        json.dump(future, f)
    loaded = shardlint.load_shard_report(path)
    assert "v2_only" not in loaded["families"]
    assert loaded["load_notes"]["skipped_count"] == 1
    assert "dp_sgd" in loaded["families"]


def test_predicted_step_time_calibration():
    from tpu_dist.obs import costmodel

    cost = {"flops_per_step": 2e9, "bytes_per_step": 1e8}
    gauges = {
        "cost.calibration_flops_per_s": 1e12,
        "cost.calibration_bytes_per_s": 1e10,
        "cost.calibration_overlap_frac": 0.5,
    }
    out = costmodel.predicted_step_time(
        cost, wire_bytes=10**7, gauges=gauges, n_devices=8
    )
    assert out["rate_source"] == "calibrated"
    assert out["compute_s"] == pytest.approx(2e-3)
    assert out["memory_s"] == pytest.approx(1e-2)
    # comm is half-hidden by the measured overlap
    assert out["predicted_step_s"] == pytest.approx(1e-2 + 0.5e-3)
    # no gauges, no chip peak (CPU): nothing priced, or spec-sheet fallback
    none = costmodel.predicted_step_time(
        {}, wire_bytes=None, gauges={}, n_devices=8
    )
    assert none == {}
    peaked = costmodel.predicted_step_time(
        cost, gauges={}, n_devices=2, peak=1e12
    )
    assert peaked["rate_source"] == "spec_peak"
    assert peaked["predicted_step_s"] == pytest.approx(1e-3)


def test_lower_and_compile_is_cached():
    import jax
    import jax.numpy as jnp

    from tpu_dist.obs import costmodel

    jitted = jax.jit(lambda x: x + 1.0)
    x = jnp.ones((8,))
    l1, c1 = costmodel.lower_and_compile(jitted, x)
    l2, c2 = costmodel.lower_and_compile(jitted, x)
    assert c1 is c2 and l1 is l2
    # a different signature is a different executable
    _, c3 = costmodel.lower_and_compile(jitted, jnp.ones((4,)))
    assert c3 is not c1


# -- one source of truth: RULES registry == docs table == CLI JSON -----------


def test_rules_registry_matches_docs_table():
    """Every rule in RULES has a `### TDxxx \\`name\\`` section in
    docs/analysis.md and vice versa — a new rule cannot land
    half-registered (the CLI JSON enumerates the same registry)."""
    doc = open(os.path.join(REPO, "docs", "analysis.md")).read()
    doc_rules = dict(re.findall(r"^### (TD\d{3}) `([\w-]+)`", doc, re.M))
    assert set(doc_rules) == set(RULES), (
        "docs/analysis.md sections vs RULES registry: "
        f"doc-only={sorted(set(doc_rules) - set(RULES))} "
        f"registry-only={sorted(set(RULES) - set(doc_rules))}"
    )
    for rid, rule in RULES.items():
        assert doc_rules[rid] == rule.name, (
            f"{rid}: doc name {doc_rules[rid]!r} != registry {rule.name!r}"
        )


def test_cli_json_enumerates_full_registry():
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "tpu_dist.analysis", "--no-jaxpr",
         "--format", "json", "tpu_dist/analysis/rules.py"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout)
    ids = [e["id"] for e in out["rules"]]
    assert ids == sorted(RULES)
    assert {"TD001", "TD008", "TD104", "TD116", "TD117"} <= set(ids)


# -- the compare gate knows the new metric -----------------------------------


def test_hlo_wire_bytes_gates_as_regression():
    from tpu_dist.obs import compare

    assert compare.direction_of("hlo_wire_bytes_per_step") == ("lower", 0.0)
    assert any(
        f == "hlo_wire_bytes_per_step" for f, _, _ in compare.BENCH_FIELDS
    )
    # higher compiled-comm bytes on the candidate side REGRESSES...
    base = {"m": {"metric": "m", "hlo_wire_bytes_per_step": 1000}}
    cand = {"m": {"metric": "m", "hlo_wire_bytes_per_step": 1200}}
    res = compare.compare_bench(base, cand, threshold=0.05)
    rows = {r["metric"]: r for r in res["rows"]}
    assert rows["m.hlo_wire_bytes_per_step"]["verdict"] == "REGRESSED"
    # ...and fewer bytes is an improvement, never flagged
    res = compare.compare_bench(cand, base, threshold=0.05)
    rows = {r["metric"]: r for r in res["rows"]}
    assert rows["m.hlo_wire_bytes_per_step"]["verdict"] == "ok"
