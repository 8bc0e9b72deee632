"""The LFM2 blocks of the pattern engine (nn/nemotron_h.py: ``C``, ``F``, ``*``
with q/k norm and rotary positions, ``E`` with gated experts, the tied head)
against the plain float32 reference (benchmarks/models/lfm2_moe.py): CPU, tiny
preset, seeded weights. And what the new fields leave as it was: the Nemotron
presets."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness.manifest import load_module  # noqa: E402
from tests.helpers import lfm2_arch as arch_of  # noqa: E402
from tpu_dist.nn.nemotron_h import (  # noqa: E402
    lfm2_24b_a2b_share,
    lfm2_moe_tiny,
    nemotron3_nano_share,
    nemotron_h_tiny,
    rms_norm,
)
from tpu_dist.obs import counters  # noqa: E402

ref = load_module(REPO, "models", "lfm2_moe")  # a copy-free import of the benchmark's file


def _tokens(m, n, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, m.vocab_size, (n, m.seq_len + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), jnp.asarray(ids[:, 1:], jnp.int32)


@pytest.fixture(scope="module")
def tiny():
    m = lfm2_moe_tiny()
    params, state = m.init(jax.random.PRNGKey(0))
    # norm weights that are not one and a bias that is not zero, so that each is compared
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(len(str(path))), a.shape)
        if "norm" in str(path[-1]) else a, params)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(5), state["router_bias"].shape)
    return m, params, {"router_bias": bias}


def _block(m, params, kind):
    at = m.pattern.index(kind)
    return params["layers"][at]


def _flat(tree):
    return np.concatenate([np.asarray(x, np.float64).ravel() for x in jax.tree_util.tree_leaves(tree)])


# -- the whole model -----------------------------------------------------------------

def test_the_reference_reads_the_presets_pattern(tiny):
    m, _, _ = tiny
    assert ref.pattern(arch_of(m)) == m.pattern == "CF*ECE"
    assert ref.pattern(arch_of(lfm2_24b_a2b_share())) == "CF*ECECECE"


def test_logits_equal_the_reference(tiny):
    m, params, state = tiny
    tok, _ = _tokens(m, 3)
    got, _ = m.apply(params, state, tok)
    want = ref.logits(arch_of(m, state["router_bias"]), params, tok)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_loss_and_every_gradient_equal_the_reference(tiny):
    m, params, state = tiny
    tok, tgt = _tokens(m, 3)
    arch = arch_of(m, state["router_bias"])
    loss, grads = jax.value_and_grad(lambda p: m.loss(p, state, tok, tgt, train=True)[0])(params)
    want, want_grads = jax.value_and_grad(lambda p: ref.loss_sum(arch, p, tok, tgt) / 3)(params)
    assert abs(float(loss) - float(want)) < 1e-5
    seen = set()
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.abs(w).max()) + 1e-8
        assert float(jnp.abs(g - w).max()) <= 1e-4 * scale + 1e-7, jax.tree_util.keystr(path)
        assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)
        seen.add(str(getattr(path[-1], "key", path[-1])))
    assert seen == {"embed", "norm_f", "norm", "in_proj", "conv_w", "out_proj", "w1", "w2", "w3",
                    "wq", "wk", "wv", "wo", "q_norm", "k_norm", "router", "w_gate", "w_up", "w_down"}


def test_the_tied_embeddings_gradient_sums_the_gather_and_the_head(tiny):
    m, params, state = tiny
    tok, tgt = _tokens(m, 2)
    assert "head" not in params
    untied = dataclasses.replace(m, tied_head=False)
    split = {**params, "head": params["embed"].T}
    g = jax.grad(lambda p: untied.loss(p, state, tok, tgt, train=True)[0])(split)
    tied = jax.grad(lambda p: m.loss(p, state, tok, tgt, train=True)[0])(params)
    assert float(jnp.abs(g["head"]).max()) > 0 and float(jnp.abs(g["embed"]).max()) > 0
    np.testing.assert_allclose(tied["embed"], g["embed"] + g["head"].T, atol=1e-7)


@pytest.mark.parametrize("recompute", [(), (1, 3), (0, 2, 4, 5)])
def test_which_blocks_are_recomputed_changes_what_is_stored_not_what_is_computed(tiny, recompute):
    m, params, state = tiny
    tok, tgt = _tokens(m, 2)
    grad = lambda d: jax.grad(lambda p: d.loss(p, state, tok, tgt, train=True)[0])(params)  # noqa: E731
    want = grad(m)                                        # every block recomputed
    got = grad(dataclasses.replace(m, recompute=recompute))
    np.testing.assert_allclose(_flat(got), _flat(want), atol=1e-7)


def test_bf16_policy_stays_near_float32(tiny):
    m, params, state = tiny
    tok, tgt = _tokens(m, 2)
    lo, _, _ = m.loss(params, state, tok, tgt, train=True, compute_dtype=jnp.bfloat16)
    hi, _, _ = m.loss(params, state, tok, tgt, train=True)
    assert lo.dtype == jnp.float32 and abs(float(lo) - float(hi)) < 2e-2 * float(hi)


def test_each_new_block_is_counted_where_it_is_traced(tiny):
    m, params, state = tiny
    tok, tgt = _tokens(m, 2)
    before = {k: counters.get(k) for k in ("conv.sites", "rope.sites", "moe.sites_gated")}
    jax.eval_shape(lambda p: m.loss(p, state, tok, tgt, train=False)[0], params)
    after = {k: counters.get(k) - v for k, v in before.items()}
    assert after == {"conv.sites": 2, "rope.sites": 1, "moe.sites_gated": 2}
    n = nemotron_h_tiny()
    p, s = n.init(jax.random.PRNGKey(0))
    jax.eval_shape(lambda p: n.loss(p, s, *_tokens(n, 2), train=False)[0], p)
    assert {k: counters.get(k) - v for k, v in before.items()} == after  # none of them in Nemotron's


# -- the gated short convolution ------------------------------------------------------------

def _conv_by_token(m, p, h):
    """The definition, a token at a time: a state of the two earlier B * u."""
    proj = h @ p["in_proj"]
    b, c, u = np.split(np.asarray(proj, np.float64), 3, axis=-1)
    k = np.asarray(p["conv_w"], np.float64)
    out = np.zeros_like(b)
    for n in range(h.shape[0]):
        state = np.zeros((m.conv_kernel, h.shape[-1]))           # v_{t-2}, v_{t-1}, v_t
        for t in range(h.shape[1]):
            state = np.concatenate([state[1:], (b[n, t] * u[n, t])[None]])
            out[n, t] = c[n, t] * (k * state).sum(axis=0)
    return out @ np.asarray(p["out_proj"], np.float64)


def test_the_short_convolution_equals_a_token_by_token_loop(tiny):
    m, params, _ = tiny
    p = _block(m, params, "C")
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 12, m.hidden))
    got = m._short_conv(p, h, jnp.float32)
    np.testing.assert_allclose(got, _conv_by_token(m, p, h), atol=1e-6)
    z = ref.sizes(arch_of(m))
    np.testing.assert_allclose(got, jax.vmap(lambda s: ref._conv(z, p, s))(h), atol=1e-6)


def test_the_short_convolution_is_causal_and_sees_two_earlier_tokens(tiny):
    m, params, _ = tiny
    p = _block(m, params, "C")
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 12, m.hidden))
    out = m._short_conv(p, h, jnp.float32)
    later = m._short_conv(p, h.at[:, 7:].add(1.0), jnp.float32)
    np.testing.assert_array_equal(out[:, :7], later[:, :7])       # output t unchanged by inputs after t
    assert float(jnp.abs(out[:, 7:] - later[:, 7:]).min(axis=-1).max()) > 0
    earlier = m._short_conv(p, h.at[:, 3].add(1.0), jnp.float32)
    moved = np.flatnonzero(np.abs(np.asarray(out - earlier)).max(axis=(0, 2)) > 0)
    assert list(moved) == [3, 4, 5]                                # taps reach t, t-1, t-2 and no further


# -- attention with q/k norms and rotary positions ---------------------------------------------

def _rotation_by_pairs(x, theta):
    """Channel i with i + D/2 as a 2-vector turned by t * theta^(-2i/D)."""
    s, _, d = x.shape
    out = np.array(x, np.float64)
    for t in range(s):
        for i in range(d // 2):
            a = t * theta ** (-2.0 * i / d)
            x1, x2 = x[t, :, i], x[t, :, i + d // 2]
            out[t, :, i] = x1 * np.cos(a) - x2 * np.sin(a)
            out[t, :, i + d // 2] = x2 * np.cos(a) + x1 * np.sin(a)
    return out


def test_norm_then_rotation_of_a_projections_heads(tiny):
    m, params, _ = tiny
    p = _block(m, params, "*")
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 16, m.attn_heads, m.attn_head_dim))
    got = m._norm_rotate(p["q_norm"], x)
    normed = np.asarray(rms_norm(p["q_norm"], x, m.eps), np.float64)
    np.testing.assert_allclose(got[0], _rotation_by_pairs(normed[0], m.rope_theta), atol=1e-5)
    plain = dataclasses.replace(m, qk_norm=False, rope_theta=None)
    np.testing.assert_array_equal(plain._norm_rotate(None, x), x)
    # a rotation keeps every pair's length, and position 0 is not turned
    unrotated = dataclasses.replace(m, rope_theta=None)._norm_rotate(p["q_norm"], x)
    np.testing.assert_allclose(got[:, 0], unrotated[:, 0], atol=1e-6)
    np.testing.assert_allclose(jnp.square(got).sum(-1), jnp.square(unrotated).sum(-1), rtol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_the_attention_block_equals_the_reference(tiny, impl):
    m, params, _ = tiny
    p = _block(m, params, "*")
    h = jax.random.normal(jax.random.PRNGKey(7), (2, m.seq_len, m.hidden))
    got = m._attention(p, h, jnp.float32, impl)
    z = ref.sizes(arch_of(m))
    np.testing.assert_allclose(got, jax.vmap(lambda s: ref._attention(z, p, s))(h), atol=2e-5)
    # the scores depend on where a token stands: the same tokens later in the
    # sequence do not give the same output as a model without positions would
    nowhere = dataclasses.replace(m, rope_theta=None)._attention(p, h, jnp.float32, impl)
    assert float(jnp.abs(got - nowhere).max()) > 1e-3


def test_a_long_causal_site_with_64_wide_heads_takes_the_tiled_kernel(monkeypatch):
    from tpu_dist.nn import attention as attn_lib

    monkeypatch.setattr(attn_lib, "_on_tpu", lambda: True)
    assert attn_lib.takes_flash_kernel("auto", True, 8192, 64)
    assert not attn_lib.takes_flash_kernel("auto", True, 8192, 32)
    assert not attn_lib.takes_flash_kernel("auto", False, 8192, 64)


# -- the gated feed-forward and the gated experts ------------------------------------------------

def test_the_gated_feed_forward(tiny):
    m, params, _ = tiny
    p = _block(m, params, "F")
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 8, m.hidden))
    want = (jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])) @ p["w2"]
    np.testing.assert_allclose(m._dense_ffn(p, h, jnp.float32), want, atol=1e-6)


def _expert_setup(held, capacity_factor=100.0, seed=0):
    m = dataclasses.replace(lfm2_moe_tiny(), experts_held=held, capacity_factor=capacity_factor)
    full = dataclasses.replace(m, experts_held=(0, m.n_experts))
    p = _block(full, full.init(jax.random.PRNGKey(seed))[0], "E")
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, m.hidden))
    return m, full, p, h


def _share(p, held):
    first, count = held
    return {**p, **{k: p[k][first:first + count] for k in ("w_gate", "w_up", "w_down")}}


def _ref_layer(m, p, bias, h):
    z = ref.sizes(arch_of(m))
    return jax.vmap(lambda seq: ref._experts(z, p, bias, seq))(h)


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (14, 2), (0, 16)])
def test_gated_expert_layer_with_a_share_equals_the_reference(held):
    m, _, p, h = _expert_setup(held)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (m.n_experts,))
    got, load, rows = m._experts(_share(p, held), bias, h, jnp.float32)
    np.testing.assert_allclose(got, _ref_layer(m, _share(p, held), bias, h), atol=2e-6)
    assert float(load.sum()) == h.shape[0] * h.shape[1] * m.top_k
    assert int(rows["rows_live"]) == int(load[held[0]:held[0] + held[1]].sum())
    assert int(rows["rows_over_cap"]) == 0


def test_every_token_sent_to_one_gated_expert_and_nothing_dropped():
    m, _, p, h = _expert_setup((0, 4))
    bias = jnp.zeros((m.n_experts,)).at[2].set(100.0)  # every token's first choice
    got, load, rows = m._experts(_share(p, (0, 4)), bias, h, jnp.float32)
    tokens = h.shape[0] * h.shape[1]
    assert float(load[2]) == tokens and int(rows["rows_live"]) >= tokens
    assert int(rows["rows_over_cap"]) == 0
    np.testing.assert_allclose(got, _ref_layer(m, _share(p, (0, 4)), bias, h), atol=2e-6)
    g = jax.grad(lambda q: m._experts(q, bias, h, jnp.float32)[0].sum())(_share(p, (0, 4)))
    w = jax.grad(lambda q: _ref_layer(m, q, bias, h).sum())(_share(p, (0, 4)))
    for name in ("w_gate", "w_up", "w_down", "router"):
        np.testing.assert_allclose(g[name], w[name], atol=1e-5)
    assert float(jnp.abs(g["w_gate"][2]).min()) > 0      # every row of the one expert's gate is reached


def test_the_eight_shares_routed_parts_sum_to_the_uncut_layer():
    """No shared expert here: a share's output is its routed part."""
    m, full, p, h = _expert_setup((0, 2))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (m.n_experts,))
    uncut = _ref_layer(full, p, bias, h)                       # all 16 experts, the reference
    total = jnp.zeros_like(uncut)
    for first in range(0, m.n_experts, 2):
        part = dataclasses.replace(m, experts_held=(first, 2))
        out, _, _ = part._experts(_share(p, (first, 2)), bias, h, jnp.float32)
        total = total + out
    assert m.n_experts // 2 == 8
    np.testing.assert_allclose(total, uncut, atol=5e-6)


def test_the_published_epsilon_is_what_the_weights_are_renormalised_with():
    from tpu_dist.parallel import expert as expert_lib

    scores = jnp.asarray([[0.5, 0.25, 0.125, 0.0625]])
    _, w = expert_lib.choose_experts(scores, jnp.zeros(4), 2, 1.0, 1e-6)
    np.testing.assert_allclose(w, [[0.5 / (0.75 + 1e-6), 0.25 / (0.75 + 1e-6)]], rtol=1e-7)
    _, w0 = expert_lib.choose_experts(scores, jnp.zeros(4), 2, 1.0)
    assert float(w0.sum()) == 1.0 and float(w.sum()) < 1.0


# -- what the new fields leave as it was ---------------------------------------------------

_NEMOTRON_LEAVES = {"A_log", "D", "conv_b", "conv_w", "dt_bias", "embed", "gnorm", "head", "in_proj",
                    "norm", "norm_f", "out_proj", "router", "shared_down", "shared_up", "w_down",
                    "w_up", "wk", "wo", "wq", "wv"}


@pytest.mark.parametrize("preset,count", [(nemotron_h_tiny, 25_228), (nemotron3_nano_share, 528_092_736),
                                          (lfm2_24b_a2b_share, 469_284_992)])
def test_parameter_trees_by_count(preset, count):
    m = preset()
    shapes = jax.eval_shape(lambda: m._init(jax.random.PRNGKey(0), jnp.float32))[0]
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == count
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in jax.tree_util.tree_leaves_with_path(shapes)}
    if "nemotron" in preset.__name__:
        assert names == _NEMOTRON_LEAVES
    assert len(shapes["layers"]) == len(m.pattern)


def test_a_tiny_nemotron_steps_loss_is_what_it_was_before_the_new_fields():
    """Read off the parent commit (PR 34) on the CPU: the defaults of the new
    fields are the Nemotron form, so its arithmetic has not moved."""
    m = nemotron_h_tiny()
    params, state = m.init(jax.random.PRNGKey(0))
    tok, tgt = _tokens(m, 3)
    loss, new, _ = m.loss(params, state, tok, tgt, train=True)
    assert float(loss) == pytest.approx(4.176233291625977, abs=2e-6)
    assert float(jnp.abs(new["router_bias"]).sum()) == pytest.approx(0.031, abs=1e-6)
    g = jax.grad(lambda p: m.loss(p, state, tok, tgt, train=True)[0])(params)
    assert float(sum(jnp.abs(x).sum() for x in jax.tree_util.tree_leaves(g))) == pytest.approx(
        85.08163452148438, rel=1e-5)


# -- through the Trainer -----------------------------------------------------------------------

def test_tiny_preset_trains_through_the_cli_and_the_loss_falls(capsys):
    from tpu_dist.cli import train as cli

    cli.main([
        "--dataset", "synthetic_tokens", "--synthetic_n", "128", "--model", "lfm2_moe_tiny",
        "--batch_size", "16", "--optimizer", "adamw", "--lr", "0.01", "--epochs", "2",
        "--log_every", "2", "--eval_every", "1", "--no_sync_bn",
    ])
    out = capsys.readouterr().out
    losses = [float(line.split("loss=")[1].split()[0]) for line in out.splitlines() if " loss=" in line]
    assert losses[-1] < 0.6 * losses[0]
    assert "samples/s" in out and " moe_load=" in out and " rows=" in out
    assert " * Acc@1" in out                      # the eval step ran over tokens too
