"""The dropless experts' combine without a scatter (ops/expert_combine.py): the
kernel in interpret mode on the CPU against XLA's ``.at[token].add``, the two
``custom_vjp``s of ``parallel/expert.py`` (the combine, the dispatch) against
autodiff of the XLA form, ``dropless_experts`` with the kernel forced against
its XLA path, and the rule and counters by which it is taken. What only the
v5e's compiler can say stands with the other compile-only tests, in
tests/test_short_attention.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.nn import nemotron_h as decoder
from tpu_dist.obs import counters
from tpu_dist.ops import expert_combine as K
from tpu_dist.parallel import expert as E

T, K_SLOTS, HELD = 1024, 3, (2, 4)  # experts 2..5 of 16 held


def _chosen(case, seed=0):
    """``[T, 3]`` distinct experts a token for each case."""
    rng = np.random.default_rng(seed)
    if case == "slots_none_one_all":      # a third of the tokens hold 0, 1 and 3 slots
        modes = np.arange(T) % 3
        far = rng.permuted(np.tile(np.arange(6, 16), (T, 1)), axis=1)[:, :3]
        near = rng.permuted(np.tile(np.arange(2, 6), (T, 1)), axis=1)[:, :3]
        return np.where((modes == 0)[:, None], far,
                        np.where((modes == 1)[:, None], np.concatenate([near[:, :1], far[:, :2]], 1), near))
    pool = {"an_expert_with_no_rows": [e for e in range(16) if e != 4],
            "no_live_row": list(range(6, 16))}.get(case, list(range(16)))
    return np.stack([rng.choice(pool, 3, replace=False) for _ in range(T)])


# (chosen, capacity): a partial last tile everywhere (no expert's share is a
# whole number of 512-row tiles); past capacity the output is NaN
_CASES = {
    "slots_none_one_all": ("slots_none_one_all", 2048),
    "an_expert_with_no_rows": ("an_expert_with_no_rows", 1024),
    "no_live_row": ("no_live_row", 1024),
    "over_capacity": ("random", 512),
}


def _layout(case):
    kind, capacity = _CASES[case]
    buf = E._buffer(jnp.asarray(_chosen(kind), jnp.int32), HELD, capacity)
    return buf, E._block_runs(buf, K.TOKEN_BLOCK)


def _xla(src, token, scale, valid, over, dtype):
    """The XLA form: ``.at[token].add`` into float32 zeros."""
    v = src.astype(jnp.float32) * (1.0 if scale is None else scale[:, None])
    out = jnp.zeros((T, src.shape[1]), jnp.float32).at[token].add(jnp.where(valid, v, 0))
    return jnp.where(over > 0, jnp.nan, out).astype(dtype)


@pytest.mark.parametrize("scaled", [True, False], ids=["combine", "dispatch_backward"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("case", list(_CASES))
def test_kernel_equals_the_scatter(case, dtype, scaled):
    """Rows outside every run hold large finite values, which must not reach
    the output; a token with no held slot gets exact zeros; over capacity the
    whole output is NaN."""
    buf, runs = _layout(case)
    token, valid, over = buf["token"], buf["valid"], buf["over"]
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    src = jax.random.normal(ks[0], (token.shape[0], 256))
    src = jnp.where(valid, src, 1e4).astype(dtype)
    scale = jax.random.uniform(ks[1], (token.shape[0],)) if scaled else None
    got = K.tokens_from_runs(src, token, scale, runs, over, T, dtype)
    want = _xla(src, token, scale, valid, over, dtype)
    assert got.shape == (T, 256) and got.dtype == dtype
    got, want = np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32))
    if case == "over_capacity":
        assert int(over) > 0 and np.isnan(got).all()
        return
    assert int(over) == 0
    held = np.zeros(T, bool)
    held[np.asarray(token)[np.asarray(valid[:, 0])]] = True
    assert not got[~held].any()  # exact zeros where no held expert took the token
    if case == "no_live_row":
        assert int(buf["live"]) == 0 and not held.any()
    tol = 1e-6 if dtype == jnp.float32 else 2 ** -7  # bf16: one rounding of another f32 order
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def test_refuses_what_fits_refuses():
    src, token, runs = jnp.zeros((1024, 200)), jnp.zeros((1024,), jnp.int32), jnp.zeros((2, 4, 2), jnp.int32)
    with pytest.raises(ValueError, match="cannot build"):
        K.tokens_from_runs(src, token, None, runs, 0, 1024, jnp.float32)
    with pytest.raises(ValueError, match="cannot build"):  # a run table for other blocks
        K.tokens_from_runs(jnp.zeros((1024, 256)), token, None, runs, 0, 1536, jnp.float32)


# -- the two custom_vjps against autodiff of the XLA form ----------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("case", ["slots_none_one_all", "an_expert_with_no_rows"])
def test_custom_vjps_equal_autodiff_of_the_xla_form(case, dtype):
    """The dispatch ``where(valid, x[token], 0)`` and the combine, values and
    gradients to x, to the rows and to the routing weights ``[T, k]``."""
    buf, runs = _layout(case)
    token, valid, over, pair = buf["token"], buf["valid"], buf["over"], buf["pair"]
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (T, 256)).astype(dtype)
    y = jnp.where(valid, jax.random.normal(ks[1], (token.shape[0], 256)), 0).astype(dtype)
    weights = jax.random.uniform(ks[2], (T, K_SLOTS)).astype(dtype)
    d_rows = jnp.where(valid, jax.random.normal(ks[3], y.shape), 0).astype(dtype)
    d_out = jax.random.normal(ks[4], (T, 256)).astype(dtype)

    def combine_kernel(y, weights):
        return E._combine(T, y, weights.reshape(-1)[pair], token, valid, runs, over)

    def combine_xla(y, weights):
        return _xla(y, token, weights.reshape(-1)[pair], valid, over, dtype)

    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    pairs = [
        (jax.vjp(lambda x: E._dispatch(T, x, token, valid, runs), x),
         jax.vjp(lambda x: jnp.where(valid, x[token], 0), x), d_rows),
        (jax.vjp(combine_kernel, y, weights), jax.vjp(combine_xla, y, weights), d_out),
    ]
    for (v_k, vjp_k), (v_x, vjp_x), ct in pairs:
        for got, want in ((v_k, v_x), *zip(vjp_k(ct), vjp_x(ct))):
            assert got.shape == want.shape and got.dtype == want.dtype
            got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
            assert np.abs(got - want).max() <= tol * np.abs(want).max()


# -- through dropless_experts ------------------------------------------------------


def _layer(gated, dtype, capacity):
    d, f, count = 256, 128, HELD[1]
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (T, d)).astype(dtype)
    chosen = jnp.asarray(_chosen("random", 1), jnp.int32)
    weights = jax.random.uniform(ks[2], (T, K_SLOTS)).astype(dtype)
    ws = [(jax.random.normal(kk, shape) * shape[1] ** -0.5).astype(dtype) for kk, shape in zip(
        ks[3:], [(count, d, f), (count, f, d), (count, d, f)])]

    def loss(x, weights, w_up, w_down, w_gate):
        out, rows = E.dropless_experts(
            x, chosen, weights, w_up, w_down, held=HELD, capacity=capacity,
            activation=jax.nn.silu, **({"w_gate": w_gate} if gated else {}))
        return jnp.sum(out.astype(jnp.float32) ** 2), rows

    return loss, (x, weights, *ws)


def _sites():
    return counters.get("moe.sites_combine_kernel"), counters.get("moe.sites_combine_xla")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
def test_dropless_experts_with_the_kernel_equals_its_xla_path(monkeypatch, gated, dtype):
    """Loss and every gradient with the combine kernel forced (interpreted
    here) against the scatter-adds; the counters say which path ran: the
    combine and the dispatch's backward, two a layer either way."""
    loss, args = _layer(gated, dtype, 1024)
    grad = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
    before = _sites()
    (l_xla, rows), g_xla = grad(*args)
    assert (_sites()[0] - before[0], _sites()[1] - before[1]) == (0, 2)
    monkeypatch.setattr(E, "takes_combine_kernel", lambda *a: True)
    before = _sites()
    (l_ker, _), g_ker = grad(*args)
    assert (_sites()[0] - before[0], _sites()[1] - before[1]) == (2, 0)
    assert int(rows["rows_over_cap"]) == 0 and 512 < int(rows["rows_live"]) < 1024
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(l_ker, l_xla, rtol=tol)
    for name, ker, xla in zip(("x", "weights", "w_up", "w_down", "w_gate"), g_ker, g_xla):
        ker, xla = (np.asarray(v.astype(jnp.float32)) for v in (ker, xla))
        assert np.abs(ker - xla).max() <= tol * np.abs(xla).max() + 1e-12, name


def test_dropless_experts_over_capacity_is_nan_on_both_paths(monkeypatch):
    loss, args = _layer(False, jnp.float32, 512)
    (l_xla, rows), _ = jax.value_and_grad(loss, has_aux=True)(*args)
    monkeypatch.setattr(E, "takes_combine_kernel", lambda *a: True)
    before = _sites()
    l_ker, _ = loss(*args)                      # forward alone: the combine, once
    assert _sites()[0] - before[0] == 1
    assert int(rows["rows_over_cap"]) > 0 and np.isnan(l_xla) and np.isnan(l_ker)


# -- which realisation the layer takes ------------------------------------------------

_FITS = [
    ("lfm2_share", 32768, 2048, 36864, jnp.bfloat16, True),
    ("nemotron_share", 16384, 2688, 16384, jnp.bfloat16, True),
    ("float32_rows", 32768, 2048, 36864, jnp.float32, True),
    ("a_partial_token_block", 32768 + 128, 2048, 36864, jnp.bfloat16, False),
    ("a_width_of_no_whole_lanes", 32768, 2000, 36864, jnp.bfloat16, False),
    ("rows_no_whole_chunk", 32768, 2048, 36864 + 64, jnp.bfloat16, False),
    ("one_byte_rows", 32768, 2048, 36864, jnp.int8, False),
    ("past_the_vmem_budget", 32768, 16384, 36864, jnp.float32, False),
]


@pytest.mark.parametrize("tokens,width,rows,dtype,ok", [c[1:] for c in _FITS], ids=[c[0] for c in _FITS])
def test_fits(tokens, width, rows, dtype, ok):
    assert K.fits(tokens, width, rows, dtype) is ok


_PRESETS = [
    ("lfm2_24b_a2b_share", decoder.lfm2_24b_a2b_share, 4 * 8192, True),
    ("nemotron3_nano_share", decoder.nemotron3_nano_share, 2 * 8192, True),
    ("lfm2_moe_tiny", decoder.lfm2_moe_tiny, 16 * 32, False),
    ("nemotron_h_tiny", decoder.nemotron_h_tiny, 16 * 32, False),
]


@pytest.mark.parametrize("preset,tokens,on_chip", [c[1:] for c in _PRESETS], ids=[c[0] for c in _PRESETS])
def test_the_rule_at_the_presets(monkeypatch, preset, tokens, on_chip):
    """Both token cells take the kernel on a TPU, by their buffer's own
    arithmetic; the tiny presets and everything off the TPU keep XLA's."""
    m = preset()
    buf = jax.eval_shape(lambda c: E._buffer(c, m.experts_held, m.buffer_rows(tokens)),
                         jax.ShapeDtypeStruct((tokens, m.top_k), jnp.int32))
    rows = buf["token"].shape[0]
    assert E.takes_combine_kernel(tokens, m.hidden, rows, jnp.bfloat16) is False  # the CPU
    monkeypatch.setattr(E, "_on_tpu", lambda: True)
    assert E.takes_combine_kernel(tokens, m.hidden, rows, jnp.bfloat16) is on_chip
