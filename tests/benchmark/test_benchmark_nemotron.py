"""The token configuration's benchmark files: the configuration against the
published widths, the analytic operations against a hand count, the data
kind, the scope-time reduction and the four readers on a synthetic trace, and
the cell rehearsed at a tiny size through the harness."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks.harness import manifest, run_cell, scopes

CONFIG = "nemotron3_nano_30b_a3b"
CELL = CONFIG + ".seq8k"
# the published config.json, as the catalog of public architectures has it
PUBLISHED = {
    "hidden_size": 2688, "num_hidden_layers": 52, "vocab_size": 131072,
    "mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
    "conv_kernel": 4, "chunk_size": 128, "expand": 2,
    "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
    "n_routed_experts": 128, "n_shared_experts": 1, "num_experts_per_tok": 6,
    "intermediate_size": 1856, "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-05, "norm_eps": 1e-05, "n_group": 1, "topk_group": 1,
    "max_position_embeddings": 262144, "rope_theta": 10000, "partial_rotary_factor": 1,
}
CUT = {"num_hidden_layers": 7, "n_routed_experts": 8, "vocab_size": 16384}


@pytest.fixture(scope="module")
def config(repo_root):
    with open(os.path.join(repo_root, "benchmarks", "configs", CONFIG + ".json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model(repo_root):
    return manifest.load_module(repo_root, "models", "nemotron_h")


def test_manifest_holds_with_the_new_cell(repo_root):
    assert manifest.check_manifest(repo_root) == []
    cell = manifest.load_cell(repo_root, CELL)
    assert (cell.chips, cell.global_batch, cell.n_train, cell.data_kind) == (1, 2, 32, "tokens")
    assert {m["name"] for m in cell.per_layer} >= {
        "ssm_scan_roofline_share", "moe_gmm_roofline_share", "lm_attn_roofline_share",
        "moe_load_max_over_mean", "mxu_roofline_share", "device_step_ms"}
    fields = manifest.train_config_fields(cell, seed=3)
    assert (fields["model"], fields["dataset"], fields["batch_size"]) == (
        "nemotron3_nano_share", "synthetic_tokens", 2)


def _run_of(per_layer, first, n):
    """The ``n`` entries from the one named ``first``, and what follows them.
    By name: the manifest's lists grow at their ends, so no PR's entries stay
    last."""
    at = [m["name"] for m in per_layer].index(first)
    return per_layer[at:at + n], per_layer[at + n:]


def test_manifest_gains_this_cells_four_metrics_after_what_was_there(repo_root):
    mine, _ = _run_of(manifest.load_manifest(repo_root)["per_layer"],
                      "ssm_scan_roofline_share", 4)
    assert [m["name"] for m in mine] == ["ssm_scan_roofline_share", "moe_gmm_roofline_share",
                                         "lm_attn_roofline_share", "moe_load_max_over_mean"]
    for m in mine:
        assert m["workloads"] == [CELL]
    assert [(m["layer"], m["moves"]) for m in mine] == 3 * [("kernels", "mfu")] + [
        ("train step", "samples_per_s")]


def test_manifest_keeps_the_three_host_readers_as_they_were_added(repo_root):
    """Every assertion of ``test_benchmark_host_readers.py::
    test_manifest_adds_the_three_readers_and_nothing_else``, with the three
    found by name where that test takes ``per_layer[-3:]``: this PR's four
    entries follow them (tests/conftest.py says why that test is an xfail)."""
    streamed = ["resnet18_cifar100.stream", "vit_b16_imagenet.stream", "vit_b16_imagenet.dp4"]
    want = ["producer_gather_share", "producer_h2d_share", "epoch_boundary_ms"]
    with open(os.path.join(repo_root, "BENCHMARK.json"), encoding="utf-8") as f:
        per_layer = json.load(f)["per_layer"]
    three, later = _run_of(per_layer, want[0], 3)
    assert [m["name"] for m in three] == want
    for m in three:
        assert m["source"] == "program_counter" and m["moves"] == "samples_per_s"
        assert m["workloads"] == streamed
    # "and nothing else": none of the three a second time, and what follows
    # them reads none of the cells they list
    assert set(want).isdisjoint(m["name"] for m in later)
    assert all(set(streamed).isdisjoint(m.get("workloads", streamed)) for m in later)
    for name in streamed:
        assert {m["name"] for m in manifest.load_cell(repo_root, name).per_layer} >= set(want)
    fused = manifest.load_cell(repo_root, "resnet18_cifar100.fused").per_layer
    assert set(want).isdisjoint(m["name"] for m in fused)
    token = manifest.load_cell(repo_root, CELL).per_layer
    assert set(want).isdisjoint(m["name"] for m in token)


@pytest.mark.parametrize("where", ["top level", "arch"])
def test_every_published_number_is_kept_but_the_three_cuts(config, where):
    block = config if where == "top level" else config["arch"]
    for key, value in PUBLISHED.items():
        assert block[key] == CUT.get(key, value), key
    assert set(CUT) < set(config["reduced"])
    assert block["hybrid_override_pattern"] == "MEMEM*E"
    assert config["arch"]["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072,
        "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert config["arch"]["published"]["hybrid_override_pattern"].startswith(
        block["hybrid_override_pattern"])


def test_the_arch_is_the_programs_preset(config):
    from tpu_dist.nn.nemotron_h import nemotron3_nano_share

    m, a = nemotron3_nano_share(), config["arch"]
    assert (m.pattern, m.vocab_size, m.seq_len, m.hidden) == (
        a["hybrid_override_pattern"], a["vocab_size"], a["seq_len"], a["hidden_size"])
    assert (m.mamba_heads, m.mamba_head_dim, m.ssm_groups, m.ssm_state, m.conv_kernel, m.chunk_size) == (
        a["mamba_num_heads"], a["mamba_head_dim"], a["n_groups"], a["ssm_state_size"],
        a["conv_kernel"], a["chunk_size"])
    assert (m.attn_heads, m.kv_heads, m.attn_head_dim) == (
        a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"])
    assert (m.n_experts, list(m.experts_held), m.top_k, m.expert_width, m.shared_width) == (
        a["published"]["n_routed_experts"], a["experts_held"], a["num_experts_per_tok"],
        a["moe_intermediate_size"], a["moe_shared_expert_intermediate_size"])
    assert (m.routed_scaling, m.eps) == (a["routed_scaling_factor"], a["layer_norm_epsilon"])
    assert a["experts_held"][1] == a["n_routed_experts"] and a["parameters"] == 528_092_736
    assert config["data"]["seq_len"] == a["seq_len"] and config["data"]["vocab_size"] == a["vocab_size"]


def test_train_flops_per_sample_equals_a_hand_count(config, model):
    """By hand, multiply-accumulates a token forward at the published widths."""
    mixer = 2688 * 10304 + 4096 * 2688                 # in_proj [z 4096|xBC 6144|dt 64], out_proj
    scan = 64.5 * (8 * 128 + 64 * 64) + 2 * 64 * 64 * 128  # chunk 128: causal half + state in, out
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2 * 32 * 128 * 8193 / 2
    expert = 2688 * 128 + 2 * 2688 * 3712 + 6 * 2 * 2688 * 1856 * 8 / 128
    head = 2688 * 16384
    per_token = 3 * (mixer + scan) + attn + 3 * expert + head
    parts = model.forward_macs_per_token(config["arch"])
    assert parts == pytest.approx(
        {"mixers": 3 * (mixer + scan), "attention": attn, "experts": 3 * expert, "head": head})
    assert model.train_flops_per_sample(config["arch"]) == pytest.approx(6 * 8192 * per_token)
    assert 2 * per_token == pytest.approx(588e6, rel=0.01)       # 588 MFLOP a token forward
    assert model.train_flops_per_sample(config["arch"]) == pytest.approx(14.4e12, rel=0.01)
    share = {k: v / sum(parts.values()) for k, v in parts.items()}
    assert share == pytest.approx(
        {"mixers": 0.41, "attention": 0.19, "experts": 0.245, "head": 0.15}, abs=0.01)


def test_region_work_is_what_the_mathematics_needs(config, model):
    arch = config["arch"]
    ops, nbytes = model.attention_work(arch, sequences=2)
    assert ops == pytest.approx(6 * 2 * 32 * 128 * (8193 / 2) * 16384)   # the causal half
    assert nbytes == pytest.approx(16384 * ((2 * 4096 + 2 * 256) * 2 * 3 + 2 * 4096))
    ops, nbytes = model.gmm_work(arch, live_rows=3 * 6144)
    assert ops == pytest.approx(6 * 3 * 6144 * 2 * 2688 * 1856)
    assert nbytes > 3 * 3 * 8 * 2 * 2688 * 1856 * 2                      # the held weights, thrice
    ops, nbytes = model.scan_work(arch, tokens=16384)
    assert ops == pytest.approx(6 * 3 * 16384 * model.scan_macs_per_token(arch))
    assert nbytes == pytest.approx(2 * 3 * 16384 * (2 * 6144 + 4 * 64 + 2 * 4096))


def _tokens_kind(repo_root):
    return manifest.load_module(repo_root, "data", "tokens")


@pytest.mark.parametrize("seed", [0, 3_000_000_019])
def test_tokens_make_repeats_per_seed(repo_root, seed):
    kind, cell = _tokens_kind(repo_root), manifest.load_cell(repo_root, CELL)
    x, y = kind.make(cell, seed)
    x2, y2 = kind.make(cell, seed)
    other, _ = kind.make(cell, seed + 1)
    assert x.shape == y.shape == (32, 8192) and x.dtype == np.int32
    np.testing.assert_array_equal(x, x2)
    np.testing.assert_array_equal(y, y2)
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    assert (x != other).mean() > 0.99 and 0 <= x.min() and x.max() < 16384
    assert kind.train_config(cell) == {"dataset": "synthetic_tokens", "synthetic_n": 8}


def test_balance_bias_brings_a_skewed_router_within_the_goal(repo_root):
    import jax
    import jax.numpy as jnp

    kind = _tokens_kind(repo_root)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    skew = jnp.linspace(-1.5, 1.5, 32)                    # some experts favoured by every token
    scores = jax.nn.sigmoid(jax.random.normal(k1, (4096, 32)) + skew)
    bias, ratio, its = kind.balance_bias(scores, 4, 1.15)
    _, chosen = jax.lax.top_k(scores, 4)
    before = np.bincount(np.asarray(chosen).ravel(), minlength=32)
    assert before.max() / before.mean() > 2.0
    assert float(ratio) <= 1.15 and 0 < int(its) < 400
    _, chosen = jax.lax.top_k(scores + bias, 4)
    after = np.bincount(np.asarray(chosen).ravel(), minlength=32)
    assert after.max() / after.mean() == pytest.approx(float(ratio))


# -- device time by scope, and the readers, on a synthetic trace ---------------------------

def _events():
    """One step: a while loop spanning two scan ops, an attention kernel, the
    grouped product, and an op of no scope; (start_ns, end_ns, instruction)."""
    return [
        (0.0, 400.0, "while.1"), (0.0, 100.0, "fusion.1"), (100.0, 300.0, "fusion.2"),
        (400.0, 900.0, "custom-call.3"), (900.0, 1000.0, "fusion.4"), (1000.0, 2000.0, "fusion.5"),
    ]


HLO = """HloModule jit_step, entry_computation_layout={()->f32[]}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %inner.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/checkpoint/ssm/scan/mul" source_file="x.py" source_line=3}
}

ENTRY %main () -> f32[] {
  %while.1 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/lm/head_loss/while"}
  %fusion.1 = f32[8]{0:T(128)} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/checkpoint/ssm/scan/dot_general" stack_frame_id=7}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.2, metadata={op_name="jit(step)/transpose(jvp(checkpoint/ssm/scan))/mul"}
  %custom-call.3 = bf16[4,8]{1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/attn/causal/pallas_call"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kOutput, calls=%fc.4, metadata={op_name="jit(step)/moe/experts/while/body/dot_general"}
  ROOT %fusion.5 = f32[]{:T(128)} fusion(%a), kind=kLoop, calls=%fc.5, metadata={op_name="jit(step)/dot_general"}
  %copy.6 = f32[8]{0} copy(%a)
}
"""


@pytest.fixture
def program_table():
    from tpu_dist.obs import hlo_scopes

    assert hlo_scopes.record(HLO) == 7
    yield hlo_scopes
    hlo_scopes.record("")


def test_the_program_names_the_ops_of_a_scope(program_table):
    assert program_table.ops_in("ssm/scan") == {"inner.1", "fusion.1", "fusion.2"}
    assert program_table.ops_in("attn/causal") == {"custom-call.3"}
    assert program_table.ops_in("moe/experts") == {"fusion.4"}
    assert program_table.ops_in("nope") == frozenset()
    assert scopes.program_ops("lm/head_loss") == {"while.1"}


def test_a_compiled_step_carries_its_scopes_to_the_table():
    import jax
    import jax.numpy as jnp

    from tpu_dist.obs import hlo_scopes

    def f(x):
        with jax.named_scope("ssm/scan"):
            y = jnp.tanh(x @ x)
        return y.sum()

    text = jax.jit(jax.grad(f)).lower(jnp.ones((8, 8))).compile().as_text()
    try:
        assert hlo_scopes.record(text) > 0 and hlo_scopes.ops_in("ssm/scan")
    finally:
        hlo_scopes.record("")
    assert hlo_scopes.recorded() == 0


def test_a_step_whose_text_cannot_be_read_still_gets_its_cost(program_table):
    """``analyze_jitted`` promises "no MFU, never an error": an executable
    that gives no text leaves an empty table and the cost it reported."""
    from tpu_dist.obs import costmodel

    class Compiled:
        def cost_analysis(self):
            return {"flops": 7.0, "bytes accessed": 3.0}

        def as_text(self):
            raise RuntimeError("no text for this executable")

    class Lowered:
        def cost_analysis(self):
            raise NotImplementedError  # the TPU client's answer

        def compile(self):
            return Compiled()

    class Jitted:
        def lower(self, *args):
            return Lowered()

    assert costmodel.analyze_jitted(Jitted()) == {"flops_per_step": 7.0, "bytes_per_step": 3.0}
    assert program_table.recorded() == 0


def test_seconds_in_sums_self_time_inside_the_window():
    ev = _events()
    assert scopes.seconds_in(ev, (0.0, 2000.0), {"fusion.1", "fusion.2"}) == pytest.approx(300e-9)
    assert scopes.seconds_in(ev, (0.0, 2000.0), {"while.1"}) == pytest.approx(100e-9)  # self time
    assert scopes.seconds_in(ev, (50.0, 650.0), {"fusion.1", "fusion.2", "custom-call.3"}) == (
        pytest.approx(500e-9))
    assert scopes.seconds_in(ev, (0.0, 2000.0), set()) == 0.0


def test_op_events_names_the_first_chips_ops(repo_root, monkeypatch, tmp_path):
    from benchmarks.harness import trace as trace_lib

    recorded = os.path.join(repo_root, "benchmarks", "harness", "testdata", "v5e_vit_b16_stream.json.gz")
    monkeypatch.setattr(trace_lib, "find_xplane", lambda d: "x")
    monkeypatch.setattr(trace_lib, "load_xplane", lambda path: trace_lib.load_trace(recorded))
    events = scopes.op_events(str(tmp_path))
    assert len(events) > 100 and all(e >= s for s, e, _ in events)
    assert any(op.startswith("fusion.") for _, _, op in events) and not any("=" in op for _, _, op in events)


def _window(repo_root, monkeypatch, events, counters):
    cell = manifest.load_cell(repo_root, CELL)
    said = []
    return {
        "cell": cell, "peaks": manifest.load_peaks(repo_root, "TPU v5 lite"),
        "traced_epoch": {"steps": 1}, "counters": counters, "say": said.append,
        # what scope_seconds keeps of a run's capture: its events, no host window
        "_scope_events": (events, None),
    }, said


def _reader(repo_root, name):
    return manifest.load_module(repo_root, "layer_metrics", name)


def test_roofline_readers_divide_the_least_time_by_the_scopes_time(
        repo_root, monkeypatch, model, program_table):
    counters = {"moe.rows_live": 2 * 18400.0, "moe.rows_balanced": 2 * 18432.0,
                "lm.tokens": 2 * 16384.0, "moe.rows_over_cap": 0.0}
    ms = 1e6  # the synthetic events' nanoseconds, stretched to milliseconds
    events = [(s * ms, e * ms, t) for s, e, t in _events()]
    window, said = _window(repo_root, monkeypatch, events, counters)
    arch = window["cell"].config["arch"]
    peak, bw = 197e12, 819e9
    ops, nbytes = model.attention_work(arch, 2)
    assert _reader(repo_root, "lm_attn_roofline_share").read(window) == pytest.approx(
        100 * max(ops / peak, nbytes / bw) / 0.5)
    ops, nbytes = model.scan_work(arch, 16384)
    assert _reader(repo_root, "ssm_scan_roofline_share").read(window) == pytest.approx(
        100 * max(ops / peak, nbytes / bw) / 0.3)
    ops, nbytes = model.gmm_work(arch, 18400.0)
    assert _reader(repo_root, "moe_gmm_roofline_share").read(window) == pytest.approx(
        100 * max(ops / peak, nbytes / bw) / 0.1)
    assert any("0.9983 of the balanced share" in line for line in said)
    assert any("attn/causal" in line and "compute-bound" in line for line in said)


@pytest.mark.parametrize("name", ["lm_attn_roofline_share", "ssm_scan_roofline_share",
                                  "moe_gmm_roofline_share", "moe_load_max_over_mean"])
def test_readers_report_nothing_where_the_program_has_no_scope_or_counter(
        repo_root, monkeypatch, name):
    """The parent of the PR that brought them: no table of scopes, no counter."""
    window, _ = _window(repo_root, monkeypatch, _events(), {})
    assert _reader(repo_root, name).read(window) is None
    window, _ = _window(repo_root, monkeypatch, None, {})
    assert _reader(repo_root, name).read(window) is None


def test_readers_report_nothing_without_a_capture(repo_root, monkeypatch, program_table):
    window, _ = _window(repo_root, monkeypatch, None, {"moe.rows_live": 1.0, "lm.tokens": 1.0})
    for name in ("lm_attn_roofline_share", "ssm_scan_roofline_share", "moe_gmm_roofline_share"):
        assert _reader(repo_root, name).read(window) is None


def test_readers_report_nothing_for_a_configuration_without_such_layers(
        repo_root, monkeypatch, program_table):
    window, _ = _window(repo_root, monkeypatch, _events(), {"moe.rows_live": 1.0, "lm.tokens": 1.0})
    window["cell"] = manifest.load_cell(repo_root, "vit_b16_imagenet.stream")
    for name in ("lm_attn_roofline_share", "ssm_scan_roofline_share", "moe_gmm_roofline_share"):
        assert _reader(repo_root, name).read(window) is None


def test_load_reader_averages_the_fetched_steps(repo_root, monkeypatch):
    window, said = _window(repo_root, monkeypatch, None, {
        "moe.steps_observed": 4.0, "moe.load_max_over_mean_sum": 4.6})
    assert _reader(repo_root, "moe_load_max_over_mean").read(window) == pytest.approx(1.15)
    assert "4 steps fetched" in said[0]


def test_scope_seconds_without_a_capture_is_none(repo_root, tmp_path, program_table):
    cell = manifest.load_cell(repo_root, CELL)
    window = {"cell": type(cell)(**{**cell.__dict__, "root": str(tmp_path)})}
    assert scopes.scope_seconds(window, "ssm/scan") is None


# -- the cell at a tiny size, through the harness ----------------------------------------------

@pytest.fixture
def token_root(tiny_root, repo_root):
    """The tiny test root with a token configuration: the tiny preset under
    the ``tokens`` data kind and the ``nemotron_h`` reference."""
    from tests.helpers import hybrid_arch
    from tpu_dist.nn.nemotron_h import nemotron_h_tiny

    m = nemotron_h_tiny()
    arch = hybrid_arch(m)
    cfg = {
        "name": "tokens_tiny_test", "arch": arch, "reference": "nemotron_h",
        "train_config": {"model": "nemotron_h_tiny", "optimizer": "adamw", "lr": 1e-4,
                         "weight_decay": 0.1, "log_every": 2},
        "data": {"kind": "tokens", "seq_len": m.seq_len, "vocab_size": m.vocab_size,
                 "epoch_steps": 6, "batch_per_chip": 4,
                 "balance": {"max_over_mean": 1.3}},
        "reference_check": {"samples_per_chip": None, "chunk": 1, "loss_rel_tol": 1e-4,
                            "sign_floor_rms": 0.5, "sign_agreement_min": 0.99,
                            "reason": "float32 on both sides here"},
    }
    bench = os.path.join(tiny_root, "benchmarks")
    shutil.copy(os.path.join(repo_root, "benchmarks", "traffic", "seq8k.json"),
                os.path.join(bench, "traffic", "seq8k.json"))
    shutil.copytree(os.path.join(repo_root, "benchmarks", "controls"),
                    os.path.join(bench, "controls"))
    with open(os.path.join(bench, "configs", "tokens_tiny_test.json"), "w", encoding="utf-8") as f:
        json.dump(cfg, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        man = json.load(f)
    man["configs"].append({"name": "tokens_tiny_test", "source": "test",
                           "file": "benchmarks/configs/tokens_tiny_test.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tokens.seq", "config": "tokens_tiny_test",
                             "traffic": "seq8k", "chips": 1, "why": "test"})
    with open(path, "w", encoding="utf-8") as f:
        json.dump(man, f)
    return tiny_root


def test_the_cell_runs_through_the_harness_at_a_tiny_size(token_root, capsys):
    assert manifest.check_manifest(token_root) == []
    result = run_cell(token_root, "tokens.seq", seed=3_000_000_019, seconds=1.0, trace=False,
                      on_chip=False)
    out = capsys.readouterr().out
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 6
    assert set(result["metrics"]) == {"samples_per_s", "mfu", "setup_s"}
    assert "router balanced on the first 4 batch(es)" in out
    line = [ln for ln in out.splitlines() if "reference check through trainer.train_step" in ln][0]
    assert "'sign_agreement': 1.0" in line


def test_the_balanced_bias_is_what_the_program_starts_from_and_the_reference_is_given(
        token_root, capsys):
    import re

    import jax

    from benchmarks.harness.adapter import Adapter

    cell = manifest.load_cell(token_root, "tokens.seq")
    ad = Adapter(cell, 11, jax.devices())
    bias = np.asarray(ad.trainer.state.bn_state["router_bias"])
    assert bias.shape == (2, 16) and np.abs(bias).max() > 0
    np.testing.assert_array_equal(cell.config["arch"]["router_bias"], bias)
    # on the four batches together, a layer: what the loop stopped at
    reached = re.findall(r"\((\d\.\d+), \d+\)", capsys.readouterr().out)
    assert len(reached) == 2 and all(float(r) <= 1.3 for r in reached)
    tokens, targets = ad.first_batch()
    _, _, stats = ad.trainer.model.loss(
        ad.trainer.state.params, ad.trainer.state.bn_state, tokens, targets, train=False)
    unbalanced = ad.trainer.model.loss(
        ad.trainer.state.params, {"router_bias": 0 * bias}, tokens, targets, train=False)[2]
    assert float(stats["maxima"]["moe_load_max_over_mean"]) < float(
        unbalanced["maxima"]["moe_load_max_over_mean"])
    cold = Adapter(manifest.load_cell(token_root, "tokens.seq"), 11, jax.devices())
    np.testing.assert_array_equal(np.asarray(cold.trainer.state.bn_state["router_bias"]), bias)


def test_the_controls_go_through_the_harness_own_comparison(token_root, capsys):
    """``benchmarks/control.py`` at the tiny size: the program as it is comes
    out correct and the reference computed in bfloat16 does not, by the limits
    of the cell's own ``reference_check``; each planted fault reads in the
    leaves it touches (64 x 32 toy matrices: the one number over all elements
    sees none of them here, which is what ``holds`` then says)."""
    from benchmarks import control

    result = control.run_controls(token_root, "tokens.seq", 3_000_000_019, leaves=True,
                                  on_chip=False)
    got = result["controls"]
    assert list(got) == ["as_it_is", "reference_bfloat16", "bf16_scan_state", "expert_skipped",
                         "bf16_router"]
    assert got["as_it_is"]["ok"] and got["as_it_is"]["sign_agreement"] == 1.0
    assert not got["reference_bfloat16"]["ok"]
    assert got["reference_bfloat16"]["loss_rel_err"] > got["as_it_is"]["loss_rel_tol"]
    leaves = {name: verdict["by_leaf"] for name, verdict in got.items()}
    assert set(leaves["as_it_is"].values()) == {1.0}
    assert {"router", "A_log", "dt_bias", "head"} <= set(leaves["as_it_is"])
    assert leaves["expert_skipped"]["w_down"] < 0.9 and leaves["expert_skipped"]["w_up"] < 0.9
    assert leaves["bf16_router"]["router"] < 0.95 and leaves["bf16_scan_state"]["A_log"] < 1.0
    assert result["holds"] is all(v["ok"] != v["must_fail"] for v in got.values())
    assert [v["must_fail"] for v in got.values()] == [False, True, True, True, False]
    assert "[control] expert_skipped:" in capsys.readouterr().out
    few = control.run_controls(token_root, "tokens.seq", 3_000_000_019,
                               only={"reference_bfloat16"}, on_chip=False)
    assert list(few["controls"]) == ["reference_bfloat16"] and few["holds"] is True
    assert "by_leaf" not in few["controls"]["reference_bfloat16"]


def test_a_lowered_reference_computes_in_the_dtype_it_names(model):
    import jax
    import jax.numpy as jnp

    from benchmarks import control
    from tests.helpers import hybrid_arch
    from tpu_dist.nn.nemotron_h import nemotron_h_tiny

    m = nemotron_h_tiny()
    params, _ = m.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, m.seq_len), 0, m.vocab_size)
    arch = hybrid_arch(m)
    low = control.lowered_reference(model, jnp.bfloat16)
    seen = jax.make_jaxpr(lambda p: low.loss_sum(arch, p, tokens, tokens))(params)
    floats = {v.aval.dtype for eqn in seen.eqns for v in eqn.outvars
              if jnp.issubdtype(v.aval.dtype, jnp.floating)}
    assert jnp.dtype(jnp.bfloat16) in floats
    whole = float(model.loss_sum(arch, params, tokens, tokens))
    assert abs(float(low.loss_sum(arch, params, tokens, tokens)) - whole) > 1e-4 * whole


def test_an_implied_update_reads_back_as_the_gradients_sign():
    """Stored in float32 as the program's parameters are: at lr 1.5e-7 what
    reads back is the sign (a parameter of 2.5 moves by one float32 step)."""
    from benchmarks import control
    from benchmarks.harness import reference

    before = {"w": np.array([[0.02, -0.5], [1.0, 2.5]], np.float32), "b": np.ones(2, np.float32)}
    grads = {"w": np.array([[1e-9, -2.0], [0.3, -1e-3]]), "b": np.array([-4.0, 5.0])}
    cfg = {"optimizer": "adamw", "weight_decay": 0.1}
    update = control.implied_update(2.5, grads, before, 1.5e-7, cfg)
    assert update["after"]["w"].dtype == np.float32
    implied, sign_only = reference.implied_gradient(update, cfg)
    assert sign_only
    np.testing.assert_array_equal(np.sign(implied["w"]), np.sign(grads["w"]))
    np.testing.assert_array_equal(np.sign(implied["b"]), np.sign(grads["b"]))
    np.testing.assert_allclose(implied["w"][0], np.sign(grads["w"][0]), atol=0.1)
    sgd = control.implied_update(2.5, grads, before, 0.1, {"optimizer": "sgd", "weight_decay": 0.0})
    np.testing.assert_allclose(reference.implied_gradient(sgd, {"weight_decay": 0.0})[0]["w"],
                               grads["w"], atol=1e-5)


def test_the_control_runs_an_image_cell_without_a_faults_file(tiny_root):
    """The generic half: a model file with ``logits`` alone (the default
    loss), float inputs lowered with the parameters, no ``controls/`` file."""
    from benchmarks import control

    got = control.run_controls(tiny_root, "tiny.stream", 5, on_chip=False)["controls"]
    assert list(got) == ["as_it_is", "reference_bfloat16"]
    assert got["as_it_is"]["ok"] and not got["as_it_is"]["must_fail"]
    assert got["reference_bfloat16"]["loss_rel_err"] > 10 * got["as_it_is"]["loss_rel_err"]
