"""The hybrid decoder (nn/nemotron_h.py) against the plain float32 reference
(benchmarks/models/nemotron_h.py): CPU, tiny preset, seeded weights."""

import dataclasses
import functools
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
from tpu_dist.nn import attention as attn_lib  # noqa: E402
from tpu_dist.nn import functional as F  # noqa: E402
from tpu_dist.nn.nemotron_h import HybridDecoderDef, nemotron_h_tiny, ssm_scan  # noqa: E402
from tpu_dist.obs import counters as counters_lib  # noqa: E402
from tests.helpers import hybrid_arch as arch_of  # noqa: E402
from tpu_dist.parallel import expert as expert_lib  # noqa: E402

ref = load_module(REPO, "models", "nemotron_h")  # a copy-free import of the benchmark's file


def _tokens(m, n, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, m.vocab_size, (n, m.seq_len + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), jnp.asarray(ids[:, 1:], jnp.int32)


@pytest.fixture(scope="module")
def tiny():
    m = nemotron_h_tiny()
    params, state = m.init(jax.random.PRNGKey(0))
    # a bias that is not zero, so that selection by score + bias is what is compared
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(5), state["router_bias"].shape)
    return m, params, {"router_bias": bias}


def _flat(tree):
    return np.concatenate([np.asarray(x, np.float64).ravel() for x in jax.tree_util.tree_leaves(tree)])


# -- the whole model -----------------------------------------------------------------

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

    def program(p):
        loss, _, _ = m.loss(p, state, tok, tgt, train=True)
        return loss

    loss, grads = jax.value_and_grad(program)(params)
    want, want_grads = jax.value_and_grad(lambda p: ref.loss_sum(arch, p, tok, tgt) / 3)(params)
    assert abs(float(loss) - float(want)) < 1e-5
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.abs(w).max()) + 1e-8
        assert float(jnp.abs(g - w).max()) <= 1e-4 * scale + 1e-7, jax.tree_util.keystr(path)
    assert float(jnp.abs(_flat(grads)).max()) > 0


@pytest.mark.parametrize("recompute", [(), (0, 2), (1, 3)])
def test_which_layers_are_recomputed_changes_what_is_stored_not_what_is_computed(tiny, recompute):
    m, params, state = tiny
    tok, tgt = _tokens(m, 2)
    grad = lambda d: jax.grad(lambda p: d.loss(p, state, tok, tgt, train=True)[0])(params)  # noqa: E731
    want = grad(m)                                        # every layer recomputed
    got = grad(dataclasses.replace(m, recompute=recompute))
    np.testing.assert_allclose(_flat(got), _flat(want), atol=1e-7)


def test_the_selection_bias_takes_no_gradient_and_moves_by_the_sign_rule(tiny):
    m, params, state = tiny
    tok, tgt = _tokens(m, 4)
    g = jax.grad(lambda s: m.loss(params, s, tok, tgt, train=True)[0])(state)
    assert float(jnp.abs(g["router_bias"]).max()) == 0.0
    _, new, _ = m.loss(params, state, tok, tgt, train=True)
    step = np.asarray(new["router_bias"] - state["router_bias"])
    assert set(np.round(np.abs(step[step != 0]) / m.bias_rate, 3)) == {1.0}
    _, same, _ = m.loss(params, state, tok, tgt, train=False)
    np.testing.assert_array_equal(same["router_bias"], state["router_bias"])


def test_bf16_policy_keeps_the_residual_in_bf16_and_stays_near_float32(tiny):
    m, params, state = tiny
    tok, tgt = _tokens(m, 2)
    lo, _, _ = m.loss(params, state, tok, tgt, train=True, compute_dtype=jnp.bfloat16)
    hi, _, _ = m.loss(params, state, tok, tgt, train=True)
    assert lo.dtype == jnp.float32 and abs(float(lo) - float(hi)) < 2e-2 * float(hi)


# -- the chunked scan ------------------------------------------------------------------

def _recurrence(x, dt, a, b, c):
    """Token by token, one sequence a time: the definition."""
    heads = x.shape[2]
    rep = heads // b.shape[2]

    def one(x, dt, b, c):
        b, c = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)

        def token(state, inp):
            x_t, dt_t, b_t, c_t = inp
            state = (jnp.exp(dt_t * a)[:, None, None] * state
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return state, jnp.sum(state * c_t[:, None, :], -1)

        zero = jnp.zeros((heads, x.shape[-1], b.shape[-1]))
        return jax.lax.scan(token, zero, (x, dt, b, c))[1]

    return jax.vmap(one)(x, dt, b, c)


def _scan_inputs(seed=0, bsz=2, s=32, heads=4, p=8, groups=2, n=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (bsz, s, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, heads)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (bsz, s, groups, n))
    c = jax.random.normal(ks[4], (bsz, s, groups, n))
    return x, dt, a, b, c


@pytest.mark.parametrize("chunk", [4, 16])
def test_chunked_scan_equals_the_recurrence(chunk):
    args = _scan_inputs()
    np.testing.assert_allclose(ssm_scan(*args, chunk), _recurrence(*args), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("chunk", [4, 16])
def test_chunked_scan_gradients_equal_the_recurrence(chunk):
    args = _scan_inputs(seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got = jax.grad(lambda *a: (ssm_scan(*a, chunk) * w).sum(), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: (_recurrence(*a) * w).sum(), argnums=(0, 1, 2, 3, 4))(*args)
    for g, e in zip(got, want):
        np.testing.assert_allclose(g, e, atol=2e-4, rtol=2e-4)


def test_a_bfloat16_state_strays_a_hundred_times_further_from_the_recurrence():
    """``state_dtype`` is what the model never lowers: decays and the carried
    state in bfloat16 (the benchmark's control) cost two digits."""
    args = _scan_inputs(seed=2, s=64)
    want = _recurrence(*args)
    err = lambda sd: float(jnp.abs(ssm_scan(*args, 16, state_dtype=sd) - want).max())  # noqa: E731
    assert err(jnp.float32) < 5e-5 and err(jnp.bfloat16) > 100 * err(jnp.float32)


def test_scan_refuses_a_ragged_sequence():
    with pytest.raises(ValueError, match="whole chunks"):
        ssm_scan(*_scan_inputs(s=30), 8)


# -- the mixer's convolution: one chain, two realisations ---------------------------------

def _conv_sites(fn):
    """(ssm.conv_sites_kernel, ssm.conv_sites_xla) that running ``fn`` adds."""
    names = ("ssm.conv_sites_kernel", "ssm.conv_sites_xla")
    before = [counters_lib.get(n) for n in names]
    out = fn()
    return out, tuple(counters_lib.get(n) - b for n, b in zip(names, before))


def _mixer_value_and_grads(m, p, h, dtype):
    w = jax.random.normal(jax.random.PRNGKey(9), h.shape)
    loss = lambda p, h: (m._mixer(p, h, dtype).astype(jnp.float32) * w).sum()  # noqa: E731
    return m._mixer(p, h, dtype), jax.grad(loss, argnums=(0, 1))(p, h)


def _interpreted(conv, *args, interpret, **kw):
    """The mixer asks for the compiled kernels (it takes them on a TPU only);
    here they are interpreted, two tiles of tokens a sequence."""
    assert interpret is False
    return conv(*args, interpret=True, tile=32, **kw)


def test_the_mixer_on_the_cpu_takes_the_xla_chain(tiny):
    m, params, _ = tiny
    h = jax.random.normal(jax.random.PRNGKey(2), (2, m.seq_len, m.hidden))
    _, sites = _conv_sites(lambda: m._mixer(params["layers"][0], h, jnp.float32))
    assert sites == (0, 1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
def test_the_mixer_with_the_conv_kernel_equals_the_mixer_with_the_chain(monkeypatch, dtype):
    """A toy mixer whose sections are whole lane blocks (2 heads of 64, one
    group of state 128: proj is gate 128 | x 128 | B 128 | C 128 | dt 2),
    with ``takes_conv_kernel`` patched true and the kernel pair interpreted:
    the output, ``dh`` and every parameter's gradient against the chain's,
    float32 to rounding, bfloat16 to a step of its own (the scan's test)."""
    from tpu_dist.nn import nemotron_h as decoder
    from tpu_dist.ops import causal_conv1d as K

    m = dataclasses.replace(
        nemotron_h_tiny(), pattern="M", hidden=32, mamba_heads=2, mamba_head_dim=64,
        ssm_groups=1, ssm_state=128, chunk_size=16, seq_len=64)
    p = m.init(jax.random.PRNGKey(3))[0]["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, m.seq_len, m.hidden)).astype(dtype)
    (y_xla, g_xla), sites = _conv_sites(lambda: _mixer_value_and_grads(m, p, h, dtype))
    assert sites[0] == 0 and sites[1] >= 1

    monkeypatch.setattr(K, "causal_conv1d", functools.partial(_interpreted, K.causal_conv1d))
    monkeypatch.setattr(decoder, "takes_conv_kernel", K.fits)
    (y_ker, g_ker), sites = _conv_sites(lambda: _mixer_value_and_grads(m, p, h, dtype))
    assert sites[0] >= 1 and sites[1] == 0

    rms = lambda t: float(jnp.sqrt(jnp.mean(jnp.square(t.astype(jnp.float32)))))  # noqa: E731
    tol = 2e-5 if dtype == jnp.float32 else 2.0 ** -7
    assert y_ker.dtype == y_xla.dtype and rms(y_ker - y_xla) <= tol * rms(y_xla)
    flat = lambda g: jax.tree_util.tree_leaves_with_path(g)  # noqa: E731
    for (path, ker), (_, xla) in zip(flat(g_ker), flat(g_xla)):
        name = jax.tree_util.keystr(path)
        assert ker.shape == xla.shape and ker.dtype == xla.dtype, name
        assert rms(ker - xla) <= tol * rms(xla) + 1e-12, name


# -- causal grouped-head attention -------------------------------------------------------

def _attention_reference(q, k, v):
    """Every query head against its key/value head's copy, causal."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _qkv(s=64, heads=4, kv=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    return (jax.random.normal(ks[0], (2, s, heads, d)), jax.random.normal(ks[1], (2, s, kv, d)),
            jax.random.normal(ks[2], (2, s, kv, d)), jax.random.normal(ks[3], (2, s, heads, d)))


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_causal_grouped_attention_forward_and_gradients(impl):
    q, k, v, w = _qkv()
    got = attn_lib.attention(q, k, v, causal=True, impl=impl)
    np.testing.assert_allclose(got, _attention_reference(q, k, v), atol=2e-5)
    grads = jax.grad(lambda *a: (attn_lib.attention(*a, causal=True, impl=impl) * w).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_attention_reference(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, e in zip(grads, want):
        assert g.shape == e.shape
        np.testing.assert_allclose(g, e, atol=5e-5)


def test_flash_xla_backward_sums_a_group_too():
    from tpu_dist.ops.flash_attention import flash_attention

    q, k, v, w = _qkv()
    f = lambda bwd: jax.grad(  # noqa: E731
        lambda *a: (flash_attention(*a, causal=True, block_q=32, block_k=16, bwd=bwd) * w).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(f("xla"), f("pallas")):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_which_kernel_a_site_takes(monkeypatch):
    # off the TPU everything left to choice is XLA
    assert not attn_lib.takes_flash_kernel("auto", True, 8192, 128)
    monkeypatch.setattr(attn_lib, "_on_tpu", lambda: True)
    assert attn_lib.takes_flash_kernel("auto", True, 8192, 128)       # a long causal site
    assert not attn_lib.takes_flash_kernel("auto", False, 8192, 128)  # no mask: XLA as before
    assert not attn_lib.takes_flash_kernel("auto", True, 196, 64)     # ViT lengths
    assert not attn_lib.takes_flash_kernel("xla", True, 8192, 128)
    assert attn_lib.takes_flash_kernel("flash", False, 196, 64)
    # a causal site never takes the whole-sequence kernel, a ViT site still does
    assert not attn_lib.takes_short_kernel("auto", None, True, 196, 12, 64, jnp.bfloat16)
    assert attn_lib.takes_short_kernel("auto", None, False, 196, 12, 64, jnp.bfloat16)


def test_mismatched_heads_are_refused():
    from tpu_dist.ops.flash_attention import flash_attention

    q, k, v, _ = _qkv(heads=4, kv=3)
    with pytest.raises(ValueError, match="do not group"):
        flash_attention(q, k, v, causal=True)


# -- the head and its loss in blocks -------------------------------------------------------

@pytest.mark.parametrize("block", [8, 64, 48])
def test_blocked_head_loss_equals_the_whole_one(block):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    h = jax.random.normal(ks[0], (64, 16))
    w = jax.random.normal(ks[1], (16, 40))
    y = jax.random.randint(ks[2], (64,), 0, 40)
    wt = (jax.random.uniform(ks[3], (64,)) > 0.2).astype(jnp.float32)

    def whole(h, w):
        logp = jax.nn.log_softmax(h @ w, -1)
        return -(jnp.take_along_axis(logp, y[:, None], -1)[:, 0] * wt).sum()

    nll, top1, top5 = F.blocked_cross_entropy(h, w, y, wt, block=block)
    assert abs(float(nll) - float(whole(h, w))) < 1e-3
    c1, c5 = F.topk_correct(h @ w, y, (1, 5))
    assert float(top1) == float(((jnp.argmax(h @ w, -1) == y) * wt).sum())
    if float(wt.min()) == 1.0:
        assert (float(top1), float(top5)) == (float(c1), float(c5))
    got = jax.grad(lambda h, w: F.blocked_cross_entropy(h, w, y, wt, block=block)[0], (0, 1))(h, w)
    for g, e in zip(got, jax.grad(whole, (0, 1))(h, w)):
        np.testing.assert_allclose(g, e, atol=1e-4)


# -- the expert layer ------------------------------------------------------------------------

def _expert_setup(held, capacity_factor=100.0, seed=0):
    m = dataclasses.replace(nemotron_h_tiny(), experts_held=held, capacity_factor=capacity_factor)
    full = dataclasses.replace(m, experts_held=(0, m.n_experts))
    p = [q for kind, q in zip(full.pattern, full.init(jax.random.PRNGKey(seed))[0]["layers"])
         if kind == "E"][0]
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, m.hidden))
    return m, full, p, h


def _share(p, held):
    first, count = held
    return {**p, "w_up": p["w_up"][first:first + count], "w_down": p["w_down"][first:first + count]}


def _ref_layer(m, p, bias, h):
    z = ref.sizes(arch_of(m))
    return jax.vmap(lambda seq: ref._experts(z, p, bias, seq))(h)


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (12, 4), (0, 16)])
def test_expert_layer_with_a_share_equals_the_reference(held):
    m, _, p, h = _expert_setup(held)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (m.n_experts,))
    got, load, rows = m._experts(_share(p, held), bias, h, jnp.float32)
    np.testing.assert_allclose(got, _ref_layer(m, _share(p, held), bias, h), atol=2e-6)
    assert float(load.sum()) == h.shape[0] * h.shape[1] * m.top_k
    assert int(rows["rows_live"]) == int(load[held[0]:held[0] + held[1]].sum())
    assert int(rows["rows_over_cap"]) == 0


def test_every_token_sent_to_one_expert_and_nothing_dropped():
    m, _, p, h = _expert_setup((0, 4))
    bias = jnp.zeros((m.n_experts,)).at[2].set(100.0)  # every token's first choice
    got, load, rows = m._experts(_share(p, (0, 4)), bias, h, jnp.float32)
    tokens = h.shape[0] * h.shape[1]
    assert float(load[2]) == tokens and int(rows["rows_live"]) >= tokens
    np.testing.assert_allclose(got, _ref_layer(m, _share(p, (0, 4)), bias, h), atol=2e-6)
    # its gradient too: every row of the one expert's weights is reached
    g = jax.grad(lambda q: m._experts(q, bias, h, jnp.float32)[0].sum())(_share(p, (0, 4)))
    w = jax.grad(lambda q: _ref_layer(m, q, bias, h).sum())(_share(p, (0, 4)))
    for name in ("w_up", "w_down", "router", "shared_up"):
        np.testing.assert_allclose(g[name], w[name], atol=1e-5)


def test_the_shares_routed_parts_plus_the_shared_expert_once_equal_the_uncut_layer():
    m, full, p, h = _expert_setup((0, 4))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (m.n_experts,))
    uncut = _ref_layer(full, p, bias, h)                       # all 16 experts, the reference
    shared = jnp.square(jax.nn.relu(h @ p["shared_up"])) @ p["shared_down"]
    total = shared
    for first in range(0, m.n_experts, 4):
        part = dataclasses.replace(m, experts_held=(first, 4))
        out, _, _ = part._experts(_share(p, (first, 4)), bias, h, jnp.float32)
        total = total + (out - shared)                          # a share's routed part
    np.testing.assert_allclose(total, uncut, atol=5e-6)


def test_an_overflowing_buffer_is_loud_not_a_silent_drop():
    m, _, p, h = _expert_setup((0, 4), capacity_factor=0.25)
    bias = jnp.zeros((m.n_experts,)).at[1].set(100.0)
    got, _, rows = m._experts(_share(p, (0, 4)), bias, h, jnp.float32)
    assert int(rows["rows_over_cap"]) > 0 and bool(jnp.isnan(got).all())


def test_the_bias_rule_moves_load_toward_the_mean():
    m, params, state = nemotron_h_tiny(), *nemotron_h_tiny().init(jax.random.PRNGKey(1))
    m = dataclasses.replace(m, bias_rate=0.02)
    tok, tgt = _tokens(m, 8, seed=4)
    step = jax.jit(lambda s: m.loss(params, s, tok, tgt, train=True)[1:])
    state, stats = step(state)
    first = float(stats["maxima"]["moe_load_max_over_mean"])
    for _ in range(60):
        state, stats = step(state)
    assert float(stats["maxima"]["moe_load_max_over_mean"]) < 0.8 * first
    assert float(stats["moe_rows_over_cap"]) == 0


def test_load_stats_count_live_against_balanced_rows():
    loads = jnp.asarray([[4.0, 0, 2, 2], [2.0, 2, 2, 2]])
    rows = [{"rows_live": jnp.int32(4), "rows_over_cap": jnp.int32(0)},
            {"rows_live": jnp.int32(4), "rows_over_cap": jnp.int32(1)}]
    s = expert_lib.load_stats(loads, rows, (0, 2))
    assert (float(s["moe_rows_live"]), float(s["moe_rows_balanced"])) == (8.0, 8.0)
    assert float(s["maxima"]["moe_load_max_over_mean"]) == 2.0 and float(s["moe_rows_over_cap"]) == 1.0
    assert expert_lib.load_stats(jnp.zeros((0, 4)), [], (0, 2)) == {}


# -- tokens through the input path and the Trainer -----------------------------------------------

def _loader(seed=5, n=24, batch=4):
    from tpu_dist.comm import mesh as mesh_lib
    from tpu_dist.data.loader import DataLoader
    from tpu_dist.data.sampler import DistributedSampler
    from tpu_dist.data.synthetic import synthetic_tokens

    x, y = synthetic_tokens(n, 16, 50, seed=1)
    mesh = mesh_lib.device_mesh([1], [mesh_lib.DATA_AXIS], jax.devices()[:1])
    sampler = DistributedSampler(n, 1, 0, shuffle=True, seed=seed, drop_last=True)
    return DataLoader(x, y, batch, sampler, mesh, seed=seed, batch_divisor=1), sampler, (x, y)


def test_token_loader_repeats_per_seed_and_epoch_and_keeps_integers():
    a, sa, (x, y) = _loader()
    b, sb, _ = _loader()
    sa.set_epoch(1)
    sb.set_epoch(1)
    first = [(np.asarray(i), np.asarray(t)) for i, t in a]
    again = [(np.asarray(i), np.asarray(t)) for i, t in b]
    assert len(first) == 6 and first[0][0].dtype == np.int32 and first[0][0].shape == (4, 16)
    for (i1, t1), (i2, t2) in zip(first, again):
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(first[0][0][:, 1:], first[0][1][:, :-1])  # targets are the next id
    sa.set_epoch(2)
    other = [np.asarray(i) for i, _ in a]
    assert any((o != f[0]).any() for o, f in zip(other, first))


def test_token_loader_takes_the_look_ahead():
    from tpu_dist.obs import counters

    loader, sampler, _ = _loader()
    cold, cold_sampler, _ = _loader()
    sampler.set_epoch(0)
    for _ in loader:
        pass
    hits = counters.get("loader.ahead_hits")
    sampler.set_epoch(1)
    cold_sampler.set_epoch(1)
    ahead, plain = next(iter(loader)), next(iter(cold))
    assert counters.get("loader.ahead_hits") == hits + 1
    np.testing.assert_array_equal(np.asarray(ahead[0]), np.asarray(plain[0]))


def test_synthetic_tokens_walk_one_permutation():
    from tpu_dist.data.synthetic import synthetic_tokens

    x, y = synthetic_tokens(6, 40, 30, seed=3)
    x2, _ = synthetic_tokens(6, 40, 30, seed=3)
    np.testing.assert_array_equal(x, x2)
    assert x.dtype == np.int32 and x.shape == y.shape == (6, 40) and x.max() < 30
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    # most steps follow the one permutation: a token's successor is nearly always the same
    pairs = {}
    for a, b in zip(x.ravel(), y.ravel()):
        pairs.setdefault(int(a), []).append(int(b))
    agree = np.mean([max(map(v.count, set(v))) / len(v) for v in pairs.values()])
    assert agree > 0.8


def test_tiny_preset_trains_through_the_cli_and_the_loss_falls(capsys):
    from tpu_dist.cli import train as cli
    from tpu_dist.obs import counters

    tr = cli.main([
        "--dataset", "synthetic_tokens", "--synthetic_n", "128", "--model", "nemotron_h_tiny",
        "--batch_size", "16", "--optimizer", "adamw", "--lr", "0.01", "--epochs", "2",
        "--log_every", "2", "--eval_every", "1", "--no_sync_bn",
    ])
    out = capsys.readouterr().out
    losses = [float(line.split("loss=")[1].split()[0]) for line in out.splitlines() if " loss=" in line]
    assert losses[-1] < 0.6 * losses[0]
    assert "samples/s" in out and "img/s" not in out and " moe_load=" in out and " rows=" in out
    assert " * Acc@1" in out                      # the eval step ran over tokens too
    assert counters.get("lm.tokens") > 0 and counters.get("moe.rows_live") > 0
    assert counters.get("moe.rows_over_cap") == 0
    assert counters.snapshot()["moe.load_max_over_mean_peak"] >= 1.0
    assert tr.state.bn_state["router_bias"].shape == (2, 16)
    assert float(jnp.abs(tr.state.bn_state["router_bias"]).max()) > 0


def test_token_model_refuses_what_it_cannot_run():
    from tpu_dist.config.config import TrainConfig
    from tpu_dist.train.trainer import Trainer

    with pytest.raises(ValueError, match="takes token ids"):
        Trainer(TrainConfig(model="nemotron_h_tiny", dataset="synthetic", synthetic_n=32, batch_size=8))
    with pytest.raises(ValueError, match="needs a token model"):
        Trainer(TrainConfig(model="vit_tiny", num_classes=10, dataset="synthetic_tokens",
                            synthetic_n=32, batch_size=8))


def test_share_preset_has_the_published_widths_and_the_counted_parameters():
    from tpu_dist.nn.nemotron_h import nemotron3_nano_share

    m = nemotron3_nano_share()
    shapes = jax.eval_shape(lambda k: m.init(k)[0], jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 528_092_736
    assert (m.hidden, m.mamba_inner, m.conv_dim, m.buffer_rows(16384)) == (2688, 4096, 6144, 12288)
    assert m.recompute == (0, 2)   # layers 1 and 3-6 keep their activations: 13.1 GiB of 15.75
    layer = {k: v.shape for k, v in shapes["layers"][1].items()}
    assert layer["router"] == (2688, 128) and layer["w_up"] == (8, 2688, 1856)
    assert shapes["layers"][0]["in_proj"].shape == (2688, 10304)
    assert shapes["layers"][5]["wk"].shape == (2688, 256)
