"""The five readers of the step's phases (ISSUE 37) on a fixture executable
text in the ``op_name`` forms jax 0.9.0 writes and synthetic op events: their
values, the partition, a ``while`` and its body, a program without the table,
and the five entries of ``BENCHMARK.json``."""

import pytest

from benchmarks.harness import manifest, phases

FUSED, TOKEN = "resnet18_cifar100.fused", "nemotron3_nano_30b_a3b.seq8k"
NAMES = ["device_fwd_ms", "device_bwd_ms", "device_opt_ms", "device_recompute_ms", "device_unscoped_share"]

_LG = "jit(step)/step/loss_grad"
HLO = f"""HloModule jit_step, entry_computation_layout={{()->f32[]}}

%body (p: f32[8]) -> f32[8] {{
  %fusion.1 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.1, metadata={{op_name="{_LG}/while/body/jvp(ssm/in_proj)/dot_general"}}
  %fusion.2 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.2, metadata={{op_name="{_LG}/while/body/transpose(jvp(ssm/in_proj))/dot_general"}}
}}

ENTRY %main () -> f32[] {{
  %while.9 = f32[8]{{0}} while(%t), condition=%cond, body=%body, metadata={{op_name="{_LG}/while"}}
  %fusion.3 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.3, metadata={{op_name="{_LG}/transpose(jvp(step/loss_grad))/jvp()/checkpoint/rematted_computation/block/norm/mul"}}
  %fusion.4 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.4, metadata={{op_name="{_LG}/transpose(jvp(step/loss_grad))/jvp()/checkpoint/block/norm/mul"}}
  %fusion.5 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.5, metadata={{op_name="{_LG}/jvp()/convert_element_type"}}
  %fusion.6 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.6, metadata={{op_name="jit(step)/step/optimizer/sub"}}
  %all-reduce.7 = f32[8]{{0}} all-reduce(%a), metadata={{op_name="jit(step)/step/grad_reduce/psum"}}
  %fusion.8 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.8, metadata={{op_name="jit(step)/step/metrics/top_k"}}
  %fusion.10 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.10, metadata={{op_name="jit(step)/data/take_crop/gather"}}
  %copy.11 = f32[8]{{0}} copy(%a)
  ROOT %fusion.12 = f32[]{{:T(128)}} fusion(%a), kind=kLoop, calls=%fc.12, metadata={{op_name="jit(step)/shard_map/add"}}
}}
"""
MS = 1e6  # nanoseconds a millisecond
# the while spans its body's two ops (30 + 50 ms) and leaves 20 of its own
EVENTS = [
    (0.0, 100 * MS, "while.9"), (10 * MS, 40 * MS, "fusion.1"), (40 * MS, 90 * MS, "fusion.2"),
    (100 * MS, 140 * MS, "fusion.3"), (140 * MS, 200 * MS, "fusion.4"), (200 * MS, 210 * MS, "fusion.5"),
    (210 * MS, 280 * MS, "fusion.6"), (280 * MS, 290 * MS, "all-reduce.7"), (290 * MS, 295 * MS, "fusion.8"),
    (300 * MS, 320 * MS, "fusion.10"), (320 * MS, 350 * MS, "copy.11"), (350 * MS, 360 * MS, "fusion.12"),
]
BUSY_S = 0.355   # 360 ms less the 5 ms nothing ran


@pytest.fixture
def program_table():
    from tpu_dist.obs import hlo_scopes

    assert hlo_scopes.record(HLO) == 11      # copy.11 carries no op_name
    yield hlo_scopes
    hlo_scopes.record("")


def _window(repo_root, events=EVENTS, steps=2):
    said = []
    return {
        "cell": manifest.load_cell(repo_root, TOKEN), "traced_epoch": {"steps": steps},
        "trace": {"chip0_busy_s": BUSY_S}, "say": said.append, "_scope_events": (events, None),
    }, said


def _read(repo_root, name, window):
    return manifest.load_module(repo_root, "layer_metrics", name).read(window)


@pytest.mark.parametrize("name, a_step", [
    ("device_fwd_ms", (30 + 20 + 10) / 2),        # the body's forward op, the while's own time, the cast
    ("device_bwd_ms", (50 + 60) / 2),             # the body's transposed op and the recomputed block's backward
    ("device_opt_ms", 70 / 2),
    ("device_recompute_ms", 40 / 2),
    # no block's scope and none of optimizer, reduce, metrics, data: the while's own 20,
    # the cast's 10, the unnamed copy's 30, the shard_map's add 10, of 355 ms busy
    ("device_unscoped_share", 100 * 70 / 355),
])
def test_each_reader_reads_its_phase(repo_root, program_table, name, a_step):
    window, _ = _window(repo_root)
    assert _read(repo_root, name, window) == pytest.approx(a_step)


def test_the_partition_sums_to_the_busy_time_and_a_while_counts_its_body_once(repo_root, program_table):
    window, said = _window(repo_root)
    parts = phases.split(window)
    assert parts["busy"] == pytest.approx(BUSY_S)
    assert sum(parts[k] for k in phases.NAMED + ("other",)) == pytest.approx(BUSY_S)
    assert parts == pytest.approx({
        "forward": 0.060, "backward": 0.110, "recompute": 0.040, "optimizer": 0.070, "grad_reduce": 0.010,
        "metrics": 0.005, "data": 0.020, "other": 0.040, "busy": BUSY_S})
    by_op = phases.self_seconds(window)
    assert by_op["while.9"] == pytest.approx(0.020) and by_op["fusion.1"] == pytest.approx(0.030)
    _read(repo_root, "device_fwd_ms", window)
    line = [s for s in said if s.startswith("phases, ms a step: ")]
    assert len(line) == 1 and "forward 30.000, backward 55.000, recompute 20.000, optimizer 35.000" in line[0]
    assert line[0].endswith("sum 177.500 beside device_step_ms 177.500")
    _read(repo_root, "device_unscoped_share", window)
    line = [s for s in said if s.startswith("outside every scope, ms a step: ")]
    assert len(line) == 1 and line[0].index("copy.11 15.000 [no op_name]") < line[0].index("while.9 10.000")


def test_a_program_that_recomputes_nothing_reads_zero_not_nothing(repo_root, program_table):
    program_table.record(HLO.replace("rematted_computation/", ""))
    window, _ = _window(repo_root)
    assert _read(repo_root, "device_recompute_ms", window) == 0.0
    assert _read(repo_root, "device_bwd_ms", window) == pytest.approx((50 + 60 + 40) / 2)


@pytest.mark.parametrize("name", NAMES)
def test_every_reader_reports_nothing_without_a_table_to_trust(repo_root, program_table, name, monkeypatch):
    """The parent of this PR (no phase table: its program opens no
    ``step/loss_grad``, its ``hlo_scopes`` has no ``ops_in_phase``), a run
    without a capture, and a table made from another tree's names."""
    program_table.record(HLO.replace("step/loss_grad", "step_local"))
    assert _read(repo_root, name, _window(repo_root)[0]) is None        # no phases in the program
    program_table.record("")
    assert _read(repo_root, name, _window(repo_root)[0]) is None        # no table at all
    program_table.record(HLO)
    assert _read(repo_root, name, _window(repo_root, events=None)[0]) is None   # no capture
    with pytest.warns(RuntimeWarning, match="vit/head"):
        program_table.record(HLO, ["step/loss_grad", "vit/head"])
    assert _read(repo_root, name, _window(repo_root)[0]) is None        # hlo_scopes.missing is 1
    program_table.record(HLO, ["step/loss_grad", "ssm/in_proj"])
    assert _read(repo_root, name, _window(repo_root)[0]) is not None
    monkeypatch.delattr(program_table, "ops_in_phase")
    assert _read(repo_root, name, _window(repo_root)[0]) is None        # the parent's module


def test_manifest_gains_the_five_entries_after_everything_that_was_there(repo_root):
    assert manifest.check_manifest(repo_root) == []
    per_layer = manifest.load_manifest(repo_root)["per_layer"]
    names = [m["name"] for m in per_layer]
    first = names.index(NAMES[0])
    assert names[first:first + 5] == NAMES and names[first - 1] == "short_conv_roofline_share"
    for m in per_layer[first:first + 5]:
        lists = [TOKEN] if m["name"] == "device_recompute_ms" else [FUSED, TOKEN]
        assert m == {"name": m["name"], "unit": "%" if m["name"] == "device_unscoped_share" else "ms",
                     "better": "lower", "source": "device_trace", "layer": "train step",
                     "moves": "samples_per_s", "workloads": lists}
    for cell, mine in ((FUSED, set(NAMES) - {"device_recompute_ms"}), (TOKEN, set(NAMES))):
        assert {m["name"] for m in manifest.load_cell(repo_root, cell).per_layer} >= mine
    for cell in ("resnet18_cifar100.stream", "vit_b16_imagenet.stream", "vit_b16_imagenet.dp4", "lfm2_24b_a2b.seq8k"):
        assert set(NAMES).isdisjoint(m["name"] for m in manifest.load_cell(repo_root, cell).per_layer)
