"""The LFM2-24B-A2B configuration's benchmark files: the configuration against
the catalog row's published keys, the program's preset against ``arch``, the
analytic operations against a hand count, the new reader on a synthetic trace,
the control's faults, and the cell rehearsed at a tiny size through the
harness."""

import json
import os
import shutil

import pytest

from benchmarks.harness import manifest, run_cell

CONFIG = "lfm2_24b_a2b"
CELL = CONFIG + ".seq8k"
PERIOD = ["full_attention", "conv", "conv", "conv"]
LAYER_TYPES = ["conv", "conv"] + 9 * PERIOD + ["full_attention", "conv"]
# the published config.json, as the catalog of public architectures has it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": LAYER_TYPES, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
CUT = {"num_hidden_layers": 5, "layer_types": LAYER_TYPES[1:6], "num_dense_layers": 1,
       "num_experts": 8, "vocab_size": 8192}


@pytest.fixture(scope="module")
def config(repo_root):
    with open(os.path.join(repo_root, "benchmarks", "configs", CONFIG + ".json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model(repo_root):
    return manifest.load_module(repo_root, "models", "lfm2_moe")


def test_manifest_holds_with_the_new_cell(repo_root):
    assert manifest.check_manifest(repo_root) == []
    man = manifest.load_manifest(repo_root)
    assert [c["name"] for c in man["configs"]][-1] == CONFIG
    assert man["workloads"][-1] == {**man["workloads"][-1], "name": CELL, "config": CONFIG,
                                    "traffic": "seq8k", "chips": 1}
    assert man["configs"][-1]["reduced"] == [*CUT, "train_tokens"]
    cell = manifest.load_cell(repo_root, CELL)
    assert (cell.chips, cell.global_batch, cell.n_train, cell.data_kind) == (1, 4, 32, "tokens")
    assert {m["name"] for m in cell.per_layer} == {
        "short_conv_roofline_share", "mxu_roofline_share", "device_step_ms", "device_idle_share"}
    assert {m["name"] for m in cell.end_to_end} == {"samples_per_s", "mfu", "setup_s"}
    fields = manifest.train_config_fields(cell, seed=3)
    assert (fields["model"], fields["dataset"], fields["batch_size"]) == (
        "lfm2_24b_a2b_share", "synthetic_tokens", 4)


def test_manifest_gains_one_metric_read_in_this_cell_alone(repo_root):
    per_layer = manifest.load_manifest(repo_root)["per_layer"]
    mine = [m for m in per_layer if m["name"] == "short_conv_roofline_share"]
    assert mine == [{"name": "short_conv_roofline_share", "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels", "moves": "mfu",
                     "workloads": [CELL]}]
    # no accepted list gained the cell: that is a benchmark PR's edit
    assert [m["name"] for m in per_layer if CELL in m.get("workloads", [])] == [
        "short_conv_roofline_share"]


@pytest.mark.parametrize("where", ["top level", "arch"])
def test_every_published_key_is_kept_but_the_cuts(config, where):
    block = config if where == "top level" else config["arch"]
    for key, value in PUBLISHED.items():
        assert block[key] == CUT.get(key, value), key
    assert set(CUT) < set(config["reduced"])
    assert config["arch"]["published"] == {key: PUBLISHED[key] for key in CUT}
    assert len(LAYER_TYPES) == 40 and LAYER_TYPES.count("full_attention") == 10
    assert block["layer_types"] == ["conv"] + PERIOD       # the dense layer, then one whole period
    assert set(config["reduced_detail"]) == set(config["reduced"])
    for key in ("initialisation", "tie_word_embeddings", "bias_rule", "lr", "optimizer", "capacity"):
        assert key in config["assumed"], key


def test_the_arch_is_the_programs_preset(config, model):
    from tpu_dist.nn.nemotron_h import lfm2_24b_a2b_share

    m, a = lfm2_24b_a2b_share(), config["arch"]
    assert (m.pattern, m.vocab_size, m.seq_len, m.hidden) == (
        model.pattern(a), a["vocab_size"], a["seq_len"], a["hidden_size"])
    assert (m.attn_heads, m.kv_heads, m.attn_head_dim) == (
        a["num_attention_heads"], a["num_key_value_heads"], a["hidden_size"] // a["num_attention_heads"])
    assert (m.n_experts, list(m.experts_held), m.top_k, m.expert_width, m.shared_width) == (
        a["published"]["num_experts"], a["experts_held"], a["num_experts_per_tok"],
        a["moe_intermediate_size"], 0)
    assert (m.dense_width, m.conv_kernel, m.rope_theta, m.eps, m.routed_scaling) == (
        a["intermediate_size"], a["conv_L_cache"], a["rope_parameters"]["rope_theta"],
        a["norm_eps"], a["routed_scaling_factor"])
    assert (m.gated_experts, m.qk_norm, m.tied_head, m.topk_eps) == (True, True, True, model.TOPK_EPS)
    assert m.rescale_layers == a["published"]["num_hidden_layers"]
    assert a["experts_held"][1] == a["num_experts"]
    assert config["data"]["seq_len"] == a["seq_len"] and config["data"]["vocab_size"] == a["vocab_size"]
    assert (config["data"]["batch_per_chip"], config["data"]["epoch_steps"]) == (4, 8)
    # what the file says is recomputed is what the preset recomputes
    assert str(list(m.recompute)) in config["remat"]


def test_parameters_equal_the_trees_count(config):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.nn.nemotron_h import lfm2_24b_a2b_share

    m = lfm2_24b_a2b_share()
    shapes = jax.eval_shape(lambda: m._init(jax.random.PRNGKey(0), jnp.float32))[0]
    count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048 + 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64 + 2048
    dense = 3 * 2048 * 11776 + 2048
    expert = 2048 * 64 + 8 * 3 * 2048 * 1536 + 2048
    by_hand = 4 * conv + attn + dense + 4 * expert + 8192 * 2048 + 2048
    assert count == by_hand == config["arch"]["parameters"] == 469_284_992


def test_train_flops_per_sample_equals_a_hand_count(config, model):
    """By hand, multiply-accumulates a token forward at the published widths."""
    conv = 2048 * 6144 + 2048 * 2048                    # in [B | C | u], out
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 32 * 64 * 8193 / 2
    dense = 3 * 2048 * 11776
    expert = 2048 * 64 + 4 * 3 * 2048 * 1536 * 8 / 64
    head = 2048 * 8192
    per_token = 4 * conv + attn + dense + 4 * expert + head
    parts = model.forward_macs_per_token(config["arch"])
    assert parts == pytest.approx({"conv": 4 * conv, "attention": attn, "dense": dense,
                                   "experts": 4 * expert, "head": head})
    assert model.train_flops_per_sample(config["arch"]) == pytest.approx(6 * 8192 * per_token)
    assert per_token == pytest.approx(203e6, rel=0.005)          # 203M MAC a token forward
    assert model.train_flops_per_sample(config["arch"]) == pytest.approx(9.97e12, rel=0.005)
    share = {k: v / sum(parts.values()) for k, v in parts.items()}
    assert share == pytest.approx(
        {"conv": 0.33, "attention": 0.135, "dense": 0.357, "experts": 0.096, "head": 0.083}, abs=0.005)


def test_region_work_is_what_the_mathematics_needs(config, model):
    arch = config["arch"]
    ops, nbytes = model.conv_work(arch, tokens=32768)
    assert ops == pytest.approx(3 * 7 * 2048 * 32768 * 4)        # 7 operations a channel forward
    assert nbytes == pytest.approx(22 * 2048 * 32768 * 4)         # ~45 kB a token and block
    assert nbytes / 819e9 == pytest.approx(7.2e-3, rel=0.01)      # ~7 ms a step at the roofline
    ops, nbytes = model.attention_work(arch, sequences=4)
    assert ops == pytest.approx(6 * 2 * 32 * 64 * (8193 / 2) * 32768)   # the causal half
    assert nbytes == pytest.approx(32768 * ((2 * 2048 + 2 * 512) * 2 * 3 + 2 * 2048))
    ops, nbytes = model.gmm_work(arch, live_rows=4 * 16384)
    assert ops == pytest.approx(6 * 4 * 16384 * 3 * 2048 * 1536)   # 3.7 TFLOP a step
    assert nbytes > 3 * 4 * 8 * 3 * 2048 * 1536 * 2                # the held weights, thrice


# -- the reader on a synthetic trace -----------------------------------------------------------

HLO = """HloModule jit_step, entry_computation_layout={()->f32[]}

ENTRY %main () -> f32[] {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.1, metadata={op_name="jit(step)/jvp(conv/short)/mul"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.2, metadata={op_name="jit(step)/transpose(jvp(conv/short))/mul"}
  %custom-call.3 = bf16[4,8]{1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/attn/causal/pallas_call"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kOutput, calls=%fc.4, metadata={op_name="jit(step)/moe/experts/while/body/dot_general"}
  %fusion.6 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.6, metadata={op_name="jit(step)/attn/rope/mul"}
  ROOT %fusion.5 = f32[]{:T(128)} fusion(%a), kind=kLoop, calls=%fc.5, metadata={op_name="jit(step)/ffn/dense/dot_general"}
}
"""
MS = 1e6  # nanoseconds a millisecond
EVENTS = [(0.0, 10 * MS, "fusion.1"), (10 * MS, 40 * MS, "fusion.2"), (40 * MS, 90 * MS, "custom-call.3"),
          (90 * MS, 190 * MS, "fusion.4"), (190 * MS, 200 * MS, "fusion.6"), (200 * MS, 400 * MS, "fusion.5")]


@pytest.fixture
def program_table():
    from tpu_dist.obs import hlo_scopes

    assert hlo_scopes.record(HLO) == 6
    yield hlo_scopes
    hlo_scopes.record("")


def _window(repo_root, events, counters):
    said = []
    return {
        "cell": manifest.load_cell(repo_root, CELL),
        "peaks": manifest.load_peaks(repo_root, "TPU v5 lite"),
        "traced_epoch": {"steps": 1}, "counters": counters, "say": said.append,
        "_scope_events": (events, None),
    }, said


def _reader(repo_root):
    return manifest.load_module(repo_root, "layer_metrics", "short_conv_roofline_share")


def test_no_new_scope_name_holds_an_old_one(program_table):
    assert program_table.ops_in("conv/short") == {"fusion.1", "fusion.2"}
    assert program_table.ops_in("attn/rope") == {"fusion.6"}
    assert program_table.ops_in("ffn/dense") == {"fusion.5"}
    assert program_table.ops_in("attn/causal") == {"custom-call.3"}
    old = ["ssm/scan", "moe/route", "moe/experts", "moe/shared", "attn/causal", "lm/head_loss"]
    for new in ("conv/short", "attn/rope", "ffn/dense"):
        assert not any(o in new or new in o for o in old)


def test_the_reader_divides_the_least_time_by_the_scopes_time(repo_root, model, program_table):
    counters = {"moe.rows_live": 65000.0, "moe.rows_balanced": 65536.0, "lm.tokens": 32768.0,
                "moe.rows_over_cap": 0.0, "moe.steps_observed": 2.0, "moe.load_max_over_mean_sum": 2.2}
    window, said = _window(repo_root, EVENTS, counters)
    arch = window["cell"].config["arch"]
    ops, nbytes = model.conv_work(arch, 32768)
    least = max(ops / 197e12, nbytes / 819e9)
    got = _reader(repo_root).read(window)
    assert got == pytest.approx(100 * least / 0.040) and 0 < got < 100
    assert any("conv/short" in line and "memory-bound" in line for line in said)
    # the three accepted readers this cell is not listed under: said, not reported
    ops, nbytes = model.attention_work(arch, 4)
    attn = 100 * max(ops / 197e12, nbytes / 819e9) / 0.050
    assert any(line.startswith("lm_attn_roofline_share (not on this cell's list): "
                               f"{attn:.4f}") for line in said)
    ops, nbytes = model.gmm_work(arch, 65000.0)
    gmm = 100 * max(ops / 197e12, nbytes / 819e9) / 0.100
    assert any(line.startswith(f"moe_gmm_roofline_share (not on this cell's list): {gmm:.4f}")
               for line in said)
    assert any(line.startswith("moe_load_max_over_mean (not on this cell's list): 1.1000")
               for line in said)


def test_the_reader_reports_nothing_where_the_program_has_no_such_scope(repo_root, program_table):
    """The parent of this PR: no table, or a table without ``conv/short``; and
    a configuration whose reference file has no ``conv_work``."""
    from tpu_dist.obs import hlo_scopes

    window, _ = _window(repo_root, None, {})
    assert _reader(repo_root).read(window) is None            # no capture
    hlo_scopes.record(HLO.replace("conv/short", "ssm/scan"))
    window, said = _window(repo_root, EVENTS, {})
    assert _reader(repo_root).read(window) is None            # no such scope
    assert any("moe_load_max_over_mean (not on this cell's list): not measured" in s for s in said)
    hlo_scopes.record("")
    window, _ = _window(repo_root, EVENTS, {})
    assert _reader(repo_root).read(window) is None            # no table at all
    hlo_scopes.record(HLO)
    window, said = _window(repo_root, EVENTS, {})
    window["cell"] = manifest.load_cell(repo_root, "nemotron3_nano_30b_a3b.seq8k")
    assert _reader(repo_root).read(window) is None and said == []


# -- the cell at a tiny size, through the harness ----------------------------------------------

@pytest.fixture
def lfm2_root(tiny_root, repo_root):
    """The tiny test root with this configuration's kind of cell: the tiny
    preset under the ``tokens`` data kind and the ``lfm2_moe`` reference."""
    from tests.helpers import lfm2_arch
    from tpu_dist.nn.nemotron_h import lfm2_moe_tiny

    m = lfm2_moe_tiny()
    cfg = {
        "name": "lfm2_tiny_test", "arch": lfm2_arch(m), "reference": "lfm2_moe",
        "train_config": {"model": "lfm2_moe_tiny", "optimizer": "adamw", "lr": 1e-4,
                         "weight_decay": 0.1, "log_every": 2},
        "data": {"kind": "tokens", "seq_len": m.seq_len, "vocab_size": m.vocab_size,
                 "epoch_steps": 6, "batch_per_chip": 4, "balance": {"max_over_mean": 1.3}},
        "reference_check": {"samples_per_chip": None, "chunk": 1, "loss_rel_tol": 1e-4,
                            "sign_floor_rms": 0.5, "sign_agreement_min": 0.99,
                            "reason": "float32 on both sides here"},
    }
    bench = os.path.join(tiny_root, "benchmarks")
    shutil.copy(os.path.join(repo_root, "benchmarks", "traffic", "seq8k.json"),
                os.path.join(bench, "traffic", "seq8k.json"))
    shutil.copytree(os.path.join(repo_root, "benchmarks", "controls"), os.path.join(bench, "controls"))
    with open(os.path.join(bench, "configs", "lfm2_tiny_test.json"), "w", encoding="utf-8") as f:
        json.dump(cfg, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        man = json.load(f)
    man["configs"].append({"name": "lfm2_tiny_test", "source": "test",
                           "file": "benchmarks/configs/lfm2_tiny_test.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "lfm2.seq", "config": "lfm2_tiny_test",
                             "traffic": "seq8k", "chips": 1, "why": "test"})
    with open(path, "w", encoding="utf-8") as f:
        json.dump(man, f)
    return tiny_root


def test_the_cell_runs_through_the_harness_at_a_tiny_size(lfm2_root, capsys):
    assert manifest.check_manifest(lfm2_root) == []
    result = run_cell(lfm2_root, "lfm2.seq", seed=3_000_000_019, seconds=1.0, trace=False,
                      on_chip=False)
    out = capsys.readouterr().out
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 6
    assert set(result["metrics"]) == {"samples_per_s", "mfu", "setup_s"}
    assert "router balanced on the first 4 batch(es)" in out
    line = [ln for ln in out.splitlines() if "reference check through trainer.train_step" in ln][0]
    assert "'sign_agreement': 1.0" in line


def test_the_control_faults_load_and_read_in_the_leaves_they_touch(lfm2_root, repo_root, capsys):
    """``benchmarks/control.py`` at the tiny size through the harness's own
    comparison: the program as it is comes out correct, the reference in
    bfloat16 does not, and each planted fault reads in the leaves it touches
    (toy matrices: which of them the one number over all elements sees is the
    chip's to say, ``PERF.md``)."""
    from benchmarks import control

    faults = manifest.load_module(repo_root, "controls", "lfm2_moe").FAULTS
    assert list(faults) == ["expert_skipped", "expert_gate_dropped", "conv_tap_dropped",
                            "no_rotation", "no_qk_norm"]
    result = control.run_controls(lfm2_root, "lfm2.seq", 3_000_000_019, leaves=True, on_chip=False)
    got = result["controls"]
    assert list(got) == ["as_it_is", "reference_bfloat16", *faults]
    assert got["as_it_is"]["ok"] and got["as_it_is"]["sign_agreement"] == 1.0
    assert not got["reference_bfloat16"]["ok"]
    leaves = {name: verdict["by_leaf"] for name, verdict in got.items()}
    assert set(leaves["as_it_is"].values()) == {1.0}
    assert {"embed", "conv_w", "q_norm", "w_gate", "w1"} <= set(leaves["as_it_is"])
    assert leaves["expert_skipped"]["w_down"] < 0.9 and leaves["expert_skipped"]["w_gate"] < 0.9
    assert leaves["expert_gate_dropped"]["w_down"] < 0.9 and leaves["expert_gate_dropped"]["w_up"] < 0.9
    assert leaves["conv_tap_dropped"]["conv_w"] < 0.9
    assert leaves["no_rotation"]["wq"] < 0.95 and leaves["no_qk_norm"]["q_norm"] < 0.95
    assert [v["must_fail"] for v in got.values()] == [False, True] + [f[0] for f in faults.values()]
    assert "[control] conv_tap_dropped:" in capsys.readouterr().out
