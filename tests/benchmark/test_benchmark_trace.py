"""The reduction from the profiler's trace to metrics: interval arithmetic on
synthetic lines, and the whole reduction pinned on traces recorded on the v5e
(``benchmarks/harness/testdata``, cut from traced windows of PR 22's runs)."""

import os

import pytest

from benchmarks.harness import trace as T


def test_interval_arithmetic():
    assert T.merge_intervals([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.union_len([(0, 2), (1, 3), (10, 11)]) == 4
    assert T.intersect_len([(0, 10)], [(2, 3), (5, 20)]) == 6
    assert T.union_len([]) == 0 and T.intersect_len([], [(0, 1)]) == 0


def test_self_time_of_a_while_excludes_its_body():
    #          while [0,100) holds two body ops; a later op stands alone
    events = [(0.0, 100.0), (10.0, 30.0), (30.0, 90.0), (100.0, 120.0)]
    assert T.self_times(events) == [20.0, 20.0, 60.0, 20.0]


FUSION = ("%fusion.46 = bf16[128,12,196,196]{2,3,1,0:T(8,128)(2,1)} fusion(f32[128,12,196,196]"
          "{2,3,1,0:T(8,128)} %get-tuple-element.746, f32[128,12,196]{2,1,0:T(8,128)S(1)} %neg.5), "
          "kind=kLoop, calls=%fused_computation.70")
TUPLE = ("%multiply_reduce_fusion.5 = (bf16[64]{0:T(256)(128)(2,1)}, bf16[4096,32,32,64]"
         "{0,3,2,1:T(8,128)(2,1)}) fusion(bf16[4096,32,32,64]{0,3,2,1:T(8,128)(2,1)} %gte.347, "
         "f32[3,3,64,64]{3,2,1,0:T(8,128)S(1)} %copy-done.21), kind=kOutput, calls=%fused_computation.123")


def test_op_names_are_parsed_from_hlo_text():
    p = T.parse_op(FUSION)
    assert (p["op"], p["opcode"], p["kind"], p["calls"]) == (
        "fusion.46", "fusion", "kLoop", "fused_computation.70")
    assert T.short_name(FUSION) == "fusion.46 fusion/kLoop bf16[128,12,196,196]"
    p = T.parse_op(TUPLE)
    assert (p["op"], p["opcode"], p["calls"]) == (
        "multiply_reduce_fusion.5", "fusion", "fused_computation.123")
    assert T.parse_op("%copy-start.573 = (s32[128]{0}, u32[]{:S(2)}) copy-start(s32[128]{0} %x)")["opcode"] == "copy-start"


def test_op_kinds():
    assert T.op_kind(FUSION, ["fused_computation.70"]) == "matmul"
    assert T.op_kind(FUSION, ["fused_computation.7"]) == "other"
    assert T.op_kind(FUSION, None) == "other"
    assert T.op_kind("%convolution.3 = bf16[8,8]{1,0} convolution(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %b)", None) == "matmul"
    for name in ("%all-reduce.1 = f32[768]{0} all-reduce(f32[768]{0} %g), replica_groups={{0,1,2,3}}",
                 "%all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} %g)",
                 "%all-reduce-done.2 = f32[8]{0} all-reduce-done(f32[8]{0} %s)",
                 "%reduce-scatter.4 = f32[2]{0} reduce-scatter(f32[8]{0} %g)",
                 "%all-gather.9 = f32[8]{0} all-gather(f32[2]{0} %g)"):
        assert T.op_kind(name, []) == "collective", name


def test_matmul_computations_from_program_text():
    text = """HloModule jit_step

%fused_computation.70 (p0: bf16[8,8], p1: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %p1 = bf16[8,8]{1,0} parameter(1)
  ROOT %convolution.1 = bf16[8,8]{1,0} convolution(bf16[8,8]{1,0} %p0, bf16[8,8]{1,0} %p1), dim_labels=bf_io->bf
}

%fused_computation.71 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %neg = f32[8]{0} negate(f32[8]{0} %p0)
}

%fused_computation.72 (a: bf16[8,8], b: bf16[8,8]) -> f32[8,8] {
  %a = bf16[8,8]{1,0} parameter(0)
  %b = bf16[8,8]{1,0} parameter(1)
  %dot.5 = f32[8,8]{1,0} dot(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %b), lhs_contracting_dims={1}
  ROOT %n = f32[8,8]{1,0} negate(f32[8,8]{1,0} %dot.5)
}

ENTRY %main.9 (x: bf16[8,8]) -> bf16[8,8] {
  %x = bf16[8,8]{1,0} parameter(0)
  ROOT %fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %x, bf16[8,8]{1,0} %x), kind=kOutput, calls=%fused_computation.70
}
"""
    assert T.matmul_computations(text) == ["fused_computation.70", "fused_computation.72"]


def _synthetic():
    ms = 1e6
    ops = [["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kOutput, calls=%fc.1", 0 * ms, 4 * ms],
           ["%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %g)", 4 * ms, 1 * ms],
           ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fc.2", 5 * ms, 1 * ms],
           # 2 ms gap while the host waits for data
           ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kOutput, calls=%fc.1", 8 * ms, 2 * ms]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                            {"name": "XLA Modules", "events": [["jit_step(1)", 0.0, 6 * ms], ["jit_step(1)", 8 * ms, 2 * ms]]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": ops[:3]}]},
    ]}


def _aligned():
    """The host's records, 100 s into its own clock: the first dispatch at
    100.0 s meets the first program run at 0 ns of the trace's clock."""
    trace = _synthetic()
    T.align_host(trace, (100.0, 100.010),
                 [("bench/dispatch", 100.0, 100.0002), ("bench/data_wait", 100.0059, 100.0079),
                  ("bench/dispatch", 100.0079, 100.0081)], first_dispatch_s=100.0)
    return trace


def test_host_records_are_put_on_the_trace_clock():
    host = _aligned()["host"]
    assert host["window"] == [pytest.approx(0.0, abs=1), pytest.approx(10e6, abs=1)]
    assert host["spans"][1] == ["bench/data_wait", pytest.approx(5.9e6, abs=1), pytest.approx(7.9e6, abs=1)]
    bare = _synthetic()
    assert T.window_of(bare) == (0.0, 10e6)  # no host records: first op to last
    assert T.window_of(_aligned()) == (pytest.approx(0.0, abs=1), pytest.approx(10e6, abs=1))


def test_reduction_of_a_synthetic_trace():
    r = T.reduce_trace(_aligned(), chips=2, matmuls=["fc.1"])
    assert r["window_s"] == pytest.approx(0.010)
    assert r["chip0_busy_s"] == pytest.approx(0.008)
    assert (r["busy_s_max"], r["busy_s_min"]) == (pytest.approx(0.008), pytest.approx(0.006))
    assert r["busy_s"] == pytest.approx(0.007)
    assert r["chip0_matmul_s"] == pytest.approx(0.006)
    assert r["chip0_collective_s"] == pytest.approx(0.001) and r["chip0_collectives"] == 1
    assert r["chip0_modules"] == 2
    assert r["device_ops"][0] == ["fusion.1 fusion/kOutput f32[8]", pytest.approx(0.006)]
    assert r["idle_gaps"] == [["bench/data_wait", pytest.approx(0.002)]]
    bare = T.reduce_trace(_synthetic(), chips=1)
    assert bare["chip0_matmul_s"] is None and bare["idle_gaps"] == [["(no annotation)", pytest.approx(0.002)]]
    assert T.reduce_trace({"planes": []}, chips=1) is None


TESTDATA = os.path.join(os.path.dirname(T.__file__), "testdata")


def test_recorded_fused_epoch_nested_whiles():
    """The first 4% of a traced epoch of ``resnet18_cifar100.fused`` on the
    v5e: the epoch's scan (``while.13``) holds the per-image crop loop
    (``while.14``), whose body holds the ops. Looked at by hand first: the
    crop's ``dynamic-update-slice.3`` is what the chip spends this stretch on."""
    trace = T.load_trace(os.path.join(TESTDATA, "v5e_resnet18_fused.json.gz"))
    planes = T.device_planes(trace)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    assert {ln["name"] for ln in planes[0]["lines"]} == {"XLA Ops", "XLA Modules"}
    assert len(trace["matmul_computations"]) == 62
    chip = T.reduce_chip(planes[0], T.window_of(trace), trace["matmul_computations"])
    assert chip["n_ops"] == 24789 and chip["n_modules"] == 1 and chip["n_collectives"] == 0
    # self times partition the busy time exactly, nesting and all
    assert sum(chip["self_ns_by_kind"].values()) == pytest.approx(chip["busy_ns"], rel=1e-12)
    assert chip["busy_ns"] == pytest.approx(94_067_839.0)
    assert chip["self_ns_by_kind"] == {"collective": 0.0, "matmul": pytest.approx(10_839_514.0),
                                       "other": pytest.approx(83_228_325.0)}
    whiles = {T.parse_op(n)["op"]: v for n, v in chip["self_ns_by_op"].items()
              if T.parse_op(n)["opcode"] == "while"}
    assert whiles == {"while.13": pytest.approx(5636.0), "while.14": pytest.approx(98773.0)}
    r = T.reduce_trace(trace, 1, trace["matmul_computations"])
    assert r["window_s"] == pytest.approx(0.09412) and r["busy_s"] == pytest.approx(0.094067839)
    assert r["chip0_matmul_s"] == pytest.approx(0.010839514) and r["chip0_collective_s"] == 0.0
    assert r["device_ops"][0] == ["dynamic-update-slice.3 dynamic-update-slice u8[4096,32,32,3]",
                                  pytest.approx(0.061706069)]
    assert r["device_ops"][1][0] == "constant_dynamic-slice_fusion.5 fusion/kLoop u8[1,32,32,3]"
    assert r["idle_gaps"] == [["(no annotation)", pytest.approx(5e-05)],
                              ["(between ops)", pytest.approx(2.161e-06)]]


def test_recorded_vit_steps():
    """Two whole steps (the 4th and 5th of 12) of a traced epoch of
    ``vit_b16_imagenet.stream`` on the v5e. The host is steps ahead of the
    chip here, so none of its spans falls inside and the chip never waits."""
    trace = T.load_trace(os.path.join(TESTDATA, "v5e_vit_b16_stream.json.gz"))
    assert trace["cut"]["cell"] == "vit_b16_imagenet.stream" and trace["host"]["spans"] == []
    assert len(trace["matmul_computations"]) == 221
    ops = T.device_planes(trace)[0]["lines"]
    assert all(T.HLO_EVENT.match(e[0]) for ln in ops if ln["name"] == "XLA Ops" for e in ln["events"])
    chip = T.reduce_chip(T.device_planes(trace)[0], T.window_of(trace), trace["matmul_computations"])
    assert chip["n_ops"] == 10442 and chip["n_modules"] == 2 and chip["n_collectives"] == 0
    assert chip["self_ns_by_kind"] == {"collective": 0.0, "matmul": pytest.approx(212_057_484.0),
                                       "other": pytest.approx(111_443_575.0)}
    r = T.reduce_trace(trace, 1, trace["matmul_computations"])
    assert r["window_s"] == pytest.approx(0.323585907)
    assert r["busy_s"] == r["chip0_busy_s"] == pytest.approx(0.323501059)
    assert r["chip0_matmul_s"] / r["chip0_busy_s"] == pytest.approx(0.6555, abs=1e-4)
    assert r["device_ops"][1] == ["fusion.28 fusion/kLoop bf16[128,12,196,196]",
                                  pytest.approx(0.001788448)]
    assert r["idle_gaps"] == [["(between ops)", pytest.approx(5.8283e-05)],
                              ["(no annotation)", pytest.approx(2.6565e-05)]]
    # without the program's text no fusion is known to hold a dot
    assert T.reduce_trace(trace, 1)["chip0_matmul_s"] is None


def test_recorded_dp4_step_collectives():
    """One whole step (the 4th of 6) of a traced epoch of ``vit_b16_imagenet.dp4``
    on four v5e chips: the gradient ``pmean`` runs as three synchronous
    all-reduces after the backward pass, with a fourth for the step's counters."""
    trace = T.load_trace(os.path.join(TESTDATA, "v5e_vit_b16_dp4.json.gz"))
    planes = T.device_planes(trace)
    assert [p["name"] for p in planes] == [f"/device:TPU:{i}" for i in range(4)]
    assert [sp[0] for sp in trace["host"]["spans"]] == ["bench/data_wait"]
    per_chip = [T.reduce_chip(p, T.window_of(trace), trace["matmul_computations"]) for p in planes]
    assert [(c["n_ops"], c["n_modules"], c["n_collectives"]) for c in per_chip] == [(4015, 1, 4)] * 4
    assert [c["collective_ns"] for c in per_chip] == [6051013.0, 6068109.0, 6045496.0, 6121287.0]
    collectives = sorted(T.parse_op(n)["op"] for ln in planes[0]["lines"] if ln["name"] == "XLA Ops"
                         for n, _, _ in ln["events"] if T.op_kind(n, None) == "collective")
    assert collectives == ["all-reduce.36", "all-reduce.37", "all-reduce.38", "all-reduce.39"]
    r = T.reduce_trace(trace, 4, trace["matmul_computations"])
    assert r["window_s"] == pytest.approx(0.171048394)
    assert r["busy_s"] == pytest.approx(0.170993752)  # the mean over the four chips
    assert (r["busy_s_max"], r["busy_s_min"]) == (pytest.approx(0.170994405), pytest.approx(0.17099329))
    assert r["chip0_collective_s"] == pytest.approx(0.006051013) and r["chip0_collectives"] == 4
    assert r["chip0_matmul_s"] == pytest.approx(0.104264079)
    assert r["device_ops"][0][0].startswith("all-reduce.37 all-reduce (f32[768], f32[768]")
    assert r["device_ops"][0][1] == pytest.approx(0.00220196)
    assert r["idle_gaps"] == [["(between ops)", pytest.approx(3.2792e-05)],
                              ["bench/data_wait", pytest.approx(2.177e-05)]]
    # one chip of the four, as a one-chip cell would read the same capture
    assert T.reduce_trace(trace, 1, trace["matmul_computations"])["busy_s"] == pytest.approx(0.170993832)
