"""The experts' grouped product as a Pallas kernel pair (ops/grouped_matmul.py):
in interpret mode on the CPU against the XLA loop of
``parallel/expert.py::grouped_matmul`` and a dense per-expert float64
product, and the rule by which ``grouped_matmul`` takes it. What only the
v5e's compiler can say stands with the other compile-only tests, in
tests/test_short_attention.py (one process may hold the TPU's library)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.nn import nemotron_h as decoder
from tpu_dist.obs import counters
from tpu_dist.ops import grouped_matmul as K
from tpu_dist.parallel import expert as E

ROWS = 128

# (a, b, experts, tile_expert, n_live)
_CASES = {
    "several_tiles_an_expert_all_live": (256, 384, 3, [0, 0, 0, 1, 2, 2], 6),
    "an_expert_with_no_tile_two_dead": (256, 384, 4, [0, 0, 2, 3, 3, 3], 4),
    "no_live_tile": (256, 384, 4, [0, 0, 2, 3, 3, 3], 0),
    "a_width_taken_whole": (256, 464, 4, [0, 1, 1, 1, 3, 3], 5),  # 464 = 3.625 x 128: 1856 at toy size
}
# the default targets take these widths whole; 128 walks two to four blocks a side
_BLOCKS = {"whole_blocks": None, "blocks_of_128": 128}


def _inputs(a, b, experts, tile_expert, n_live, dtype, transpose):
    ks = jax.random.split(jax.random.PRNGKey(a + b + n_live), 3)
    tiles = len(tile_expert)
    x = jax.random.normal(ks[0], (tiles, ROWS, a)).astype(dtype)
    x = x.at[n_live:].set(jnp.nan)  # what a dead tile holds must not matter
    w = (jax.random.normal(ks[1], (experts, b, a) if transpose else (experts, a, b))
         * a ** -0.5).astype(dtype)
    dy = jax.random.normal(ks[2], (tiles, ROWS, b)).astype(dtype).at[n_live:].set(jnp.nan)
    return x, w, dy, jnp.asarray(tile_expert, jnp.int32)


def _dense(x, w, dy, tile_expert, n_live, transpose):
    """Float64, expert by expert: the product, and the weight gradient."""
    x, w, dy = (np.asarray(t.astype(jnp.float32), np.float64) for t in (x, w, dy))
    y, dw = np.zeros(dy.shape), np.zeros(w.shape)
    for t, e in enumerate(tile_expert[:n_live]):
        y[t] = x[t] @ (w[e].T if transpose else w[e])
        dw[e] += dy[t].T @ x[t] if transpose else x[t].T @ dy[t]
    return y, dw


def _err(got, want):
    return float(np.abs(np.asarray(got.astype(jnp.float32), np.float64) - want).max())


@pytest.mark.parametrize("blocks", list(_BLOCKS.values()), ids=list(_BLOCKS))
@pytest.mark.parametrize("transpose", [False, True], ids=["as_stored", "read_transposed"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16_operands"])
@pytest.mark.parametrize("case", list(_CASES.values()), ids=list(_CASES))
def test_kernel_pair_equals_the_loop_and_the_dense_product(monkeypatch, case, dtype, transpose, blocks):
    """``gmm`` (either way round) and ``tgmm`` against the XLA loop and the
    float64 product of the same (rounded) inputs. Dead tiles hold NaN and
    give exact zeros; an expert with no live tile gets an exactly zero slab."""
    if blocks:
        monkeypatch.setattr(K, "BLOCK", blocks)
    a, b, experts, tile_expert, n_live = case
    x, w, dy, te = _inputs(a, b, experts, tile_expert, n_live, dtype, transpose)
    y_true, dw_true = _dense(x, w, dy, tile_expert, n_live, transpose)

    y = K.gmm(x, w, te, n_live, transpose)
    dw = K.tgmm(x, dy, te, n_live, experts, w.dtype, transpose)
    y_loop, vjp = jax.vjp(lambda w: E.grouped_matmul(x, w, te, n_live, transpose), w)
    dw_loop, = vjp(dy)
    assert (y.shape, y.dtype, dw.shape, dw.dtype) == (y_loop.shape, dtype, w.shape, dtype)
    assert bool(jnp.all(y[n_live:] == 0)) and bool(jnp.all(y_loop[n_live:] == 0))
    for e in set(range(experts)) - set(tile_expert[:n_live]):
        assert bool(jnp.all(dw[e] == 0)), e
    assert bool(jnp.all(jnp.isfinite(dw)))
    for got, loop, true in ((y, y_loop, y_true), (dw, dw_loop, dw_true)):
        scale = np.abs(true).max() + 1e-30
        if dtype == jnp.float32:
            assert _err(got, true) <= 2e-5 * scale + 1e-6
        # the operands are the loop's, the float32 sum only in another order:
        # no further from the truth than the loop, with a fifth of room
        assert _err(got, true) <= 1.2 * _err(loop, true) + 1e-6 * scale + 1e-6


def test_kernels_refuse_what_fits_refuses():
    x = jnp.zeros((2, 64, 256))
    with pytest.raises(ValueError, match="cannot take"):
        K.gmm(x, jnp.zeros((2, 256, 256)), jnp.zeros((2,), jnp.int32), 2)
    with pytest.raises(ValueError, match="cannot take"):
        K.tgmm(jnp.zeros((2, 128, 72)), jnp.zeros((2, 128, 256)), jnp.zeros((2,), jnp.int32), 2,
               2, jnp.float32)


# -- through dropless_experts ------------------------------------------------------


def _layer(gated, dtype, width):
    t, d, k, count = 1500, 256, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(width), 6)
    x = jax.random.normal(ks[0], (t, d)).astype(dtype)
    chosen = jax.random.randint(ks[1], (t, k), 0, 8)
    chosen = jnp.where(chosen >= 6, 2, chosen)  # the first held expert: 3/8 of the pairs, three tiles
    weights = jax.random.uniform(ks[2], (t, k)).astype(dtype)
    ws = [(jax.random.normal(kk, shape) * shape[1] ** -0.5).astype(dtype) for kk, shape in zip(
        ks[3:], [(count, d, width), (count, width, d), (count, d, width)])]

    def loss(x, weights, w_up, w_down, w_gate):
        out, rows = E.dropless_experts(
            x, chosen, weights, w_up, w_down, held=(2, count), capacity=3072,
            activation=jax.nn.silu, **({"w_gate": w_gate} if gated else {}))
        return jnp.sum(out.astype(jnp.float32) ** 2), rows

    return loss, (x, weights, *ws)


@pytest.mark.parametrize("width", [384, 464], ids=["width_in_blocks", "width_taken_whole"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
def test_gradients_through_dropless_experts_equal_the_loops(monkeypatch, gated, dtype, width):
    """The layer as the models call it, 512-row tiles, some of them dead and
    one expert's share larger than a tile: loss and every gradient with the
    kernel pair forced (interpreted here) against the loop's."""
    loss, args = _layer(gated, dtype, width)
    grad = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
    before = counters.get("moe.sites_gmm_kernel")
    (l_loop, rows), g_loop = grad(*args)
    assert counters.get("moe.sites_gmm_kernel") == before
    monkeypatch.setattr(E, "takes_gmm_kernel", lambda rows, a, b, dtype: True)
    (l_ker, _), g_ker = grad(*args)
    products = 3 if gated else 2
    assert counters.get("moe.sites_gmm_kernel") - before == 2 * products  # forward, and dx
    assert int(rows["rows_over_cap"]) == 0 and 2048 < int(rows["rows_live"]) < 2560  # 6 of 10 tiles
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(l_ker, l_loop, rtol=tol)
    for name, ker, loop in zip(("x", "weights", "w_up", "w_down", "w_gate"), g_ker, g_loop):
        ker, loop = (np.asarray(v.astype(jnp.float32)) for v in (ker, loop))
        assert np.abs(ker - loop).max() <= tol * np.abs(loop).max() + 1e-12, name
    if not gated:
        assert not np.any(np.asarray(g_ker[4].astype(jnp.float32)))


# -- which realisation grouped_matmul takes -------------------------------------------

_FITS = [
    # PERF.md, PR 36 (the block-size table): the chip took all four products of both cells
    ("lfm2_up_and_gate", 512, 2048, 1536, jnp.bfloat16, True),
    ("lfm2_down", 512, 1536, 2048, jnp.bfloat16, True),
    ("nemotron_up_1856_whole", 512, 2688, 1856, jnp.bfloat16, True),
    ("nemotron_down", 512, 1856, 2688, jnp.bfloat16, True),
    ("float32_operands", 512, 2048, 1536, jnp.float32, True),
    ("a_short_tile", 64, 2048, 1536, jnp.bfloat16, False),
    ("rows_no_multiple_of_128", 520, 2048, 1536, jnp.bfloat16, False),
    ("narrower_than_a_lane_group", 512, 32, 16, jnp.bfloat16, False),
    ("whole_but_no_sublane_groups", 512, 2048, 1000, jnp.bfloat16, False),
    ("whole_and_past_the_vmem_budget", 512, 2048, 4112, jnp.float32, False),
]


@pytest.mark.parametrize("rows,a,b,dtype,ok", [c[1:] for c in _FITS], ids=[c[0] for c in _FITS])
def test_fits(rows, a, b, dtype, ok):
    assert K.fits(rows, a, b, dtype) is ok
    assert K.fits(rows, b, a, dtype) is ok  # the gradients swap the two


def _tile_and_widths(m, tokens):
    """(rows of a tile, hidden, expert width) of a preset's expert layer at
    ``tokens`` tokens, by ``dropless_experts``' own arithmetic."""
    capacity = m.buffer_rows(tokens)
    return min(E.GROUP_TILE, -(-capacity // 8) * 8), m.hidden, m.expert_width


_PRESETS = [
    ("lfm2_24b_a2b_share", decoder.lfm2_24b_a2b_share, 4 * 8192, True),
    ("nemotron3_nano_share", decoder.nemotron3_nano_share, 2 * 8192, True),
    ("lfm2_moe_tiny", decoder.lfm2_moe_tiny, 16 * 32, False),
    ("nemotron_h_tiny", decoder.nemotron_h_tiny, 16 * 32, False),
]


@pytest.mark.parametrize("preset,tokens,on_chip", [c[1:] for c in _PRESETS], ids=[c[0] for c in _PRESETS])
def test_the_rule_at_the_presets(monkeypatch, preset, tokens, on_chip):
    """Both token cells pass the rule on a TPU; the tiny presets and
    everything off the TPU keep the loop."""
    rows, d, f = _tile_and_widths(preset(), tokens)
    assert E.takes_gmm_kernel(rows, d, f, jnp.bfloat16) is False  # the CPU
    monkeypatch.setattr(E, "_on_tpu", lambda: True)
    assert E.takes_gmm_kernel(rows, d, f, jnp.bfloat16) is on_chip
    assert E.takes_gmm_kernel(rows, f, d, jnp.bfloat16) is on_chip


def _sites(fn):
    """(moe.sites_gmm_kernel, moe.sites_gmm_xla) that tracing ``fn`` adds."""
    before = counters.get("moe.sites_gmm_kernel"), counters.get("moe.sites_gmm_xla")
    fn()
    return (counters.get("moe.sites_gmm_kernel") - before[0],
            counters.get("moe.sites_gmm_xla") - before[1])


def _trace_product(rows=512, a=256, b=384, transpose=False):
    x = jax.ShapeDtypeStruct((6, rows, a), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, b, a) if transpose else (4, a, b), jnp.bfloat16)
    te = jax.ShapeDtypeStruct((6,), jnp.int32)
    return lambda: jax.eval_shape(
        lambda x, w, te: E.grouped_matmul(x, w, te, 4, transpose), x, w, te)


_RULE = [
    ("off_the_tpu", False, {}, (0, 1)),
    ("on_the_tpu", True, {}, (1, 0)),
    ("on_the_tpu_read_transposed", True, {"transpose": True}, (1, 0)),
    ("a_short_tile", True, {"rows": 64}, (0, 1)),
    ("shape_fits_refuses", True, {"b": 72}, (0, 1)),
]


@pytest.mark.parametrize("on_tpu,kw,want", [c[1:] for c in _RULE], ids=[c[0] for c in _RULE])
def test_selection_and_counters(monkeypatch, on_tpu, kw, want):
    monkeypatch.setattr(E, "_on_tpu", lambda: on_tpu)
    assert _sites(_trace_product(**kw)) == want
