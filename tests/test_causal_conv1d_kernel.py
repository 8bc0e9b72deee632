"""The mixers' depthwise convolution as a Pallas kernel pair
(ops/causal_conv1d.py): in interpret mode on the CPU against the XLA chain of
``nn/nemotron_h.py::_mixer``, and the rule by which the mixer takes it. What
only the v5e's compiler can say stands with the other compile-only tests, in
tests/test_short_attention.py (one process may hold the TPU's library)."""

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

from tests.test_nemotron_h import _conv_sites  # noqa: E402
from tpu_dist.nn import nemotron_h as decoder  # noqa: E402
from tpu_dist.ops import causal_conv1d as K  # noqa: E402

f32 = jnp.float32


def chain(x, w, bias, borders, activation):
    """``_mixer``'s XLA chain over the columns ``borders`` span, split as the
    kernel splits: pad, float32, the taps in order, bias, activation, cast."""
    lo, hi = borders[0], borders[-1]
    k, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x[..., lo:hi], ((0, 0), (k - 1, 0), (0, 0))).astype(f32)
    conv = sum(padded[:, i:i + s] * w[i] for i in range(k))
    if bias is not None:
        conv = conv + bias
    y = (jax.nn.silu(conv) if activation == "silu" else conv).astype(x.dtype)
    return (x[..., :lo], *jnp.split(y, [b - lo for b in borders[1:-1]], axis=-1), x[..., hi:])


def _inputs(bsz, seq, width, borders, k, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    channels = borders[-1] - borders[0]
    bound = k ** -0.5
    x = jax.random.normal(ks[0], (bsz, seq, width)).astype(dtype)
    w = jax.random.uniform(ks[1], (k, channels), f32, -bound, bound)
    bias = jax.random.uniform(ks[2], (channels,), f32, -bound, bound)
    return x, w, bias, ks[3]


def _value_and_grads(f, args, key):
    """Every output, and the gradients of a random weighting of all of them."""
    outs = f(*args)
    cts = [jax.random.normal(k, o.shape) for k, o in zip(jax.random.split(key, len(outs)), outs)]
    loss = lambda *a: sum((o.astype(f32) * c).sum() for o, c in zip(f(*a), cts))  # noqa: E731
    return outs, jax.grad(loss, argnums=tuple(range(len(args))))(*args)


def _rms(x):
    return float(jnp.sqrt(jnp.mean(jnp.square(x.astype(f32)))))


# (batch, tokens, tile, columns of x, borders, taps)
_SHAPES = {
    "one_tile_one_block": (1, 32, 32, 128, (0, 128), 4),
    "tiles_batch_blocks": (2, 96, 32, 256, (0, 256), 4),
    "three_taps": (2, 64, 16, 256, (0, 128, 256), 3),
    "sections_in_place": (2, 96, 32, 640, (128, 384, 512), 4),   # as the mixer: gate | x | B | rest
    "a_chunk_of_16_rows": (1, 48, 48, 128, (0, 128), 4),
    "eight_taps": (1, 64, 32, 128, (0, 128), 8),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16_operands"])
@pytest.mark.parametrize("shape", list(_SHAPES.values()), ids=list(_SHAPES))
def test_kernel_pair_equals_the_xla_chain(shape, dtype):
    """Values, ``dx``, ``dw`` and ``dbias``. Both forms hold every value
    between the read and the write in float32 and add the taps in one order,
    so they differ by the rounding of silu and of the sums' order: float32
    to 1e-5 of a value's size, bfloat16 outputs to one step of theirs."""
    bsz, seq, tile, width, borders, k = shape
    x, w, bias, key = _inputs(bsz, seq, width, borders, k, dtype)
    kernel = lambda x, w, b: K.causal_conv1d(  # noqa: E731
        x, w, b, borders=borders, activation="silu", tile=tile)
    y_ker, g_ker = _value_and_grads(kernel, (x, w, bias), key)
    y_xla, g_xla = _value_and_grads(lambda *a: chain(*a, borders, "silu"), (x, w, bias), key)
    assert len(y_ker) == len(borders) + 1
    step = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-5
    for name, ker, xla in [(f"y{i}", a, b) for i, (a, b) in enumerate(zip(y_ker, y_xla))] + [
            (n, a, b) for n, a, b in zip(("dx", "dw", "dbias"), g_ker, g_xla)]:
        assert ker.shape == xla.shape and ker.dtype == xla.dtype, name
        if ker.size:
            tol = 1e-5 if name in ("dw", "dbias") else step  # float32 sums in either dtype
            assert _rms(ker - xla) <= tol * _rms(xla), name
    np.testing.assert_array_equal(y_ker[0], x[..., :borders[0]])   # the columns outside pass through
    np.testing.assert_array_equal(y_ker[-1], x[..., borders[-1]:])


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
def test_without_an_activation_it_is_the_plain_convolution(bias):
    """What a later caller hands it (LFM2's short convolution has neither
    bias nor activation): the weighted sum alone, and its gradients."""
    borders = (0, 256)
    x, w, b, key = _inputs(2, 64, 256, borders, 3, f32, seed=1)
    args = (x, w, b) if bias else (x, w)
    kernel = lambda x, w, b=None: K.causal_conv1d(x, w, b, tile=32)  # noqa: E731
    y_ker, g_ker = _value_and_grads(kernel, args, key)
    y_xla, g_xla = _value_and_grads(lambda x, w, b=None: chain(x, w, b, borders, None), args, key)
    for ker, xla in zip((*y_ker, *g_ker), (*y_xla, *g_xla)):
        assert ker.shape == xla.shape
        if ker.size:
            assert _rms(ker - xla) <= 1e-5 * _rms(xla)


def test_an_impulse_crosses_a_tile_border_and_not_a_sequence_border():
    """x is zero but for the last row of sequence 0's first tile: the first
    ``k - 1`` rows of the next tile read it, each through its own tap, and
    sequence 1 (the next rows of the batch) reads nothing. Back: a cotangent
    on the next tile's first rows alone reaches that row."""
    tile, k = 32, 4
    x, w, _, _ = _inputs(2, 3 * tile, 128, (0, 128), k, f32, seed=2)
    x = jnp.zeros_like(x).at[0, tile - 1].set(1.0)
    conv = lambda x: K.causal_conv1d(x, w, tile=tile)[1]  # noqa: E731
    y = conv(x)
    for j in range(k):                                    # row tile-1+j reads it through tap k-1-j
        np.testing.assert_allclose(y[0, tile - 1 + j], w[k - 1 - j], rtol=1e-6)
    assert float(jnp.abs(y[0, tile + k - 1:]).max()) == 0.0
    assert float(jnp.abs(y[0, :tile - 1]).max()) == 0.0
    assert float(jnp.abs(y[1]).max()) == 0.0
    # the last row of sequence 0 set as well: sequence 1's first rows still read zeros
    y = conv(x.at[0, -1].set(1.0))
    assert float(jnp.abs(y[1]).max()) == 0.0
    ct = jnp.zeros_like(x).at[0, tile:tile + k - 1].set(1.0)
    dx = jax.grad(lambda x: (conv(x) * ct).sum())(x)
    np.testing.assert_allclose(dx[0, tile - 1], w[:k - 1].sum(axis=0), rtol=1e-6)
    assert float(jnp.abs(dx[1]).max()) == 0.0
    # and a cotangent on sequence 1's first rows reaches no row of sequence 0
    dx = jax.grad(lambda x: (conv(x) * jnp.zeros_like(x).at[1, :k].set(1.0)).sum())(x)
    assert float(jnp.abs(dx[0]).max()) == 0.0


# -- the rule ----------------------------------------------------------------------

_FITS = [
    ("the_cells_shape", (8192, (4096, 8192, 9216, 10240), 4, jnp.bfloat16), True),
    ("float32", (64, (0, 128), 4, jnp.float32), True),
    ("a_length_that_is_no_whole_tile", (8200, (0, 128), 4, jnp.bfloat16), False),
    ("a_width_that_is_no_lane_block", (64, (0, 192), 4, jnp.bfloat16), False),
    ("a_section_that_starts_inside_a_lane_block", (64, (64, 192), 4, jnp.bfloat16), False),
    ("nine_taps", (64, (0, 128), 9, jnp.bfloat16), False),
    ("borders_that_do_not_ascend", (64, (256, 128), 4, jnp.bfloat16), False),
    ("one_byte_operands", (64, (0, 128), 4, jnp.int8), False),
]


@pytest.mark.parametrize("args,want", [c[1:] for c in _FITS], ids=[c[0] for c in _FITS])
def test_fits_is_computed_from_the_shape(args, want):
    assert K.fits(*args) is want


@pytest.mark.parametrize("args", [c[1] for c in _FITS if not c[2] and c[1][3] != jnp.int8],
                         ids=[c[0] for c in _FITS if not c[2] and c[1][3] != jnp.int8])
def test_the_kernel_refuses_what_fits_refuses(args):
    seq, borders, k, dtype = args
    x = jnp.zeros((1, seq, max(borders)), dtype)
    w = jnp.zeros((k, abs(borders[-1] - borders[0])), f32)
    with pytest.raises(ValueError, match="causal_conv1d cannot take"):
        K.causal_conv1d(x, w, borders=borders)


@pytest.mark.parametrize("kw,why", [
    ({"tile": 24}, "a tile that is no multiple of 16"),
    ({"tile": 48}, "a tile the sequence is not whole in"),
    ({"tile": 8192, "seq": 8192}, "a tile beyond the VMEM budget"),
    ({"borders": (0, 256)}, "borders beyond x"),
    ({"borders": (0, 128), "w": jnp.zeros((4, 256), f32)}, "taps over other channels than the borders span"),
    ({"activation": "gelu"}, "an activation it has not"),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else "")
def test_the_kernel_refuses_a_call_it_cannot_keep(kw, why):
    kw = dict(kw)
    w = kw.pop("w", jnp.zeros((4, 128), f32))
    with pytest.raises(ValueError):
        K.causal_conv1d(jnp.zeros((1, kw.pop("seq", 64), 128), f32), w, **kw)


def _trace_mixer(m, seq, dtype=jnp.bfloat16):
    p = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0))[0]["layers"][0])
    h = jax.ShapeDtypeStruct((1, seq, m.hidden), dtype)
    return lambda: jax.eval_shape(lambda p, h: m._mixer(p, h, dtype), p, h)


_RULE = [
    ("off_the_tpu", False, decoder.nemotron3_nano_share, 256, (0, 1)),
    ("on_the_tpu", True, decoder.nemotron3_nano_share, 256, (1, 0)),
    ("a_length_that_is_no_whole_tile", True, decoder.nemotron3_nano_share, 136, (0, 1)),
    ("widths_that_are_no_lane_blocks", True, decoder.nemotron_h_tiny, 32, (0, 1)),
]


@pytest.mark.parametrize("on_tpu,preset,seq,want", [c[1:] for c in _RULE], ids=[c[0] for c in _RULE])
def test_selection_and_counters(monkeypatch, on_tpu, preset, seq, want):
    """Traced and not run, at the share preset's widths and the tiny preset's
    (whose sections are 32 and 16 columns wide)."""
    monkeypatch.setattr(decoder, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(decoder, "takes_scan_kernel", lambda *a: False)  # the scan: its own tests
    m = dataclasses.replace(preset(), chunk_size=8)               # whole chunks of any of these lengths
    assert _conv_sites(_trace_mixer(m, seq))[1] == want
