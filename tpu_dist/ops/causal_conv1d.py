"""A depthwise causal convolution over a column range of a projection, with its
bias and activation, as a Pallas kernel pair that reads the projection in place
and keeps every float32 value in VMEM.

``nn/nemotron_h.py::_mixer`` as an XLA chain (slice ``xbc`` out of ``proj``,
pad, cast to float32, four shifted products, bias, silu, cast, split into x,
B, C) is five passes forward and four backward through float32 ``[B, S, C]``
arrays, two of which live from a layer's forward to its backward: 7.8 GB a
layer where the mathematics moves 1.0 (``PERF.md``, PR 38). Here::

    y[b, t, c] = act(sum_i w[i, c] * x[b, t - (k-1) + i, first + c] + bias[c])

with ``x`` zero before ``t = 0`` of its own sequence, the taps added in that
order, everything between the read and the write in float32. A grid step
takes a tile of tokens by a block of channels of one sequence; the ``k - 1``
rows before the tile come as a second, one-sublane-tile block of the same
array, and rows are shifted in float32 in vector registers. The columns are
read through the block's index map, so no slice of ``x`` is copied first, and
each section of ``borders`` is written as an array of its own, channel-minor:
what ``ops/ssm_scan.py`` takes.

Backward: ``conv`` is recomputed from ``x`` in VMEM, ``g = dy * act'(conv)``;
``dx`` is the anti-causal form of the same taps applied to ``g`` (the tokens
are walked from the last tile to the first, the first rows of the tile
after carried in a VMEM scratch); ``dw`` and ``dbias`` are summed in float32
over batch and sequence in the output block, which stays in VMEM for a
channel block's whole walk and is written once. The residuals are the
inputs: no float32 ``[B, S, C]`` array reaches HBM in either direction.

One ``custom_vjp`` spans the whole of ``x``: the columns outside ``borders``
pass through (views XLA reads in place), so that the cotangent of ``x`` is one
concatenation of the sections' and the pass-through columns' cotangents,
written once, and not a padded array added to another.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_HALO = 8                     # float32 rows a sublane tile holds: the most a tap may reach back
MAX_TAPS = _HALO
# What one grid step may hold, under the 16 MiB a v5e kernel gets by default.
VMEM_BUDGET_BYTES = 12 * 2**20
# Tokens and channels a grid step takes at most, and the rows the kernels hold
# in registers at once: on the chip, one mixer layer of the Nemotron share
# forward + backward, 1024 x 512 x 32 took 0.74 + 1.30 ms, 512 x 512 0.82 +
# 1.33, 512 x 1024 0.80 + 1.49, 512 x 256 1.02 + 1.44, 16 or 64 rows 0.85-0.90
# + 1.44-1.46 (PERF.md, PR 38).
TOKEN_TILE = 1024
CHANNEL_BLOCK = 512
_ROWS = 32
_ACTIVATIONS = (None, "silu")


def _token_tile(seq: int, taps: int, itemsize: int) -> Optional[int]:
    """The largest multiple of 16 rows (a bfloat16 sublane tile) that divides
    ``seq``, is at most ``TOKEN_TILE`` and keeps a step inside the VMEM
    budget; None where there is none."""
    return max((t for t in range(16, min(seq, TOKEN_TILE) + 1, 16) if seq % t == 0
                and vmem_bytes(t, CHANNEL_BLOCK, taps, itemsize) <= VMEM_BUDGET_BYTES), default=None)


def _channel_block(first: int, width: int) -> int:
    """The largest multiple of 128 lanes, at most ``CHANNEL_BLOCK``, in whole
    blocks of which the section starts and ends."""
    return max(c for c in range(_LANES, CHANNEL_BLOCK + 1, _LANES) if first % c == 0 and width % c == 0)


def vmem_bytes(tile: int, block: int, taps: int, itemsize: int) -> int:
    """VMEM one grid step of the backward kernel (the larger) needs: x, dy
    and dx double-buffered, the rows before the tile, the taps and the bias,
    the float32 sums, the carried rows, and a dozen float32 row chunks."""
    blocks = 2 * (3 * tile + 32 // itemsize) * block * itemsize
    small = (2 * (taps + 1) + 2 * _HALO * (taps + 1) + _HALO) * block * 4
    return blocks + small + 12 * _ROWS * block * 4


def fits(seq: int, borders: Sequence[int], taps: int, dtype) -> bool:
    """Whether the pair can convolve the sections ``borders`` (column
    borders, ascending) of sequences of ``seq`` tokens with ``taps`` taps:
    whole tiles of tokens, sections that start and end on 128-lane blocks,
    at most ``MAX_TAPS`` taps, 2- or 4-byte operands, and the working set
    inside the VMEM budget."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in (2, 4) or not 1 <= taps <= MAX_TAPS:
        return False
    if len(borders) < 2 or any(b % _LANES for b in borders) or any(
            a >= b for a, b in zip(borders, borders[1:])):
        return False
    return _token_tile(seq, taps, itemsize) is not None


# -- what the two kernels share ---------------------------------------------------


def _rows(tile: int) -> int:
    """Rows of a tile the kernels take at once."""
    return max(r for r in (_ROWS, 16) if tile % r == 0)


def _rows_before(prev_ref, first_tile):
    """The ``_HALO`` rows before the tile in float32; zeros before a
    sequence's first token."""
    prev = prev_ref[0].astype(jnp.float32)
    return jnp.where(first_tile, 0.0, prev[prev.shape[0] - _HALO:])


def _shift_down(x, before, j):
    """``x[t - j]`` for the rows of ``x [rows, c]``, the first ``j`` from
    ``before [_HALO, c]``, the rows that precede it."""
    if j == 0:
        return x
    rolled = pltpu.roll(x, j, 0)
    row = lax.broadcasted_iota(jnp.int32, before.shape, 0)
    head = jnp.where(row < j, pltpu.roll(before, j, 0), rolled[:_HALO])
    return jnp.concatenate([head, rolled[_HALO:]], axis=0)


def _shift_up(g, after, j):
    """``g[t + j]`` for the rows of ``g [rows, c]``, the last ``j`` from
    ``after [_HALO, c]``, the rows that follow it."""
    if j == 0:
        return g
    rows = g.shape[0]
    rolled = pltpu.roll(g, rows - j, 0)
    row = lax.broadcasted_iota(jnp.int32, after.shape, 0)
    tail = jnp.where(row >= _HALO - j, pltpu.roll(after, _HALO - j, 0), rolled[rows - _HALO:])
    return jnp.concatenate([rolled[:rows - _HALO], tail], axis=0)


def _taps(x, before, w):
    """The ``k`` shifted views ``x[t - (k-1) + i]`` and their weighted sum,
    added in the order of ``i``."""
    k = w.shape[0]
    views = [_shift_down(x, before, k - 1 - i) for i in range(k)]
    conv = views[0] * w[0:1]
    for i in range(1, k):
        conv = conv + views[i] * w[i:i + 1]
    return views, conv


def _fold(v):
    """``[rows, c] -> [_HALO, c]``: the sum of the sublane tiles, whole
    registers added; the last eight rows are summed by XLA after the call."""
    out = v[:_HALO]
    for r in range(_HALO, v.shape[0], _HALO):
        out = out + v[r:r + _HALO]
    return out


# -- the kernels ------------------------------------------------------------------


def _fwd_kernel(x_ref, prev_ref, w_ref, b_ref, y_ref, *, activation):
    tile = x_ref.shape[1]
    rows = _rows(tile)
    w, b = w_ref[...], b_ref[...]

    def chunk(r, before):
        r0 = pl.multiple_of(r * rows, rows)
        x = x_ref[0, pl.ds(r0, rows), :].astype(jnp.float32)
        conv = _taps(x, before, w)[1] + b
        y = jax.nn.silu(conv) if activation == "silu" else conv
        y_ref[0, pl.ds(r0, rows), :] = y.astype(y_ref.dtype)
        return x[rows - _HALO:]

    lax.fori_loop(0, tile // rows, chunk, _rows_before(prev_ref, pl.program_id(2) == 0))


def _bwd_kernel(x_ref, prev_ref, w_ref, b_ref, dy_ref, dx_ref, sums_ref, after_ref, *, activation):
    """One tile of the reverse walk. ``after_ref`` carries ``g``'s first rows
    of the tile after in, this tile's out. ``sums_ref [(k+1) * _HALO, c]``:
    a tap's ``dw`` in its eight rows, ``dbias`` in the last eight."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)      # nothing follows a sequence's last token

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    f32 = jnp.float32
    tile = x_ref.shape[1]
    rows = _rows(tile)
    halo = prev_ref.shape[1]
    w, b = w_ref[...], b_ref[...]
    k = w.shape[0]

    def chunk(r0, before, after):
        x = x_ref[0, pl.ds(r0, rows), :].astype(f32)
        g = dy_ref[0, pl.ds(r0, rows), :].astype(f32)
        views, conv = _taps(x, before, w)
        if activation == "silu":
            conv = conv + b
            s = jax.nn.sigmoid(conv)
            g = g * (s * (1.0 + conv * (1.0 - s)))
        for i in range(k):
            sums_ref[i * _HALO:(i + 1) * _HALO, :] += _fold(g * views[i])
        sums_ref[k * _HALO:, :] += _fold(g)
        dx = _shift_up(g, after, k - 1) * w[0:1]
        for i in range(1, k):
            dx = dx + _shift_up(g, after, k - 1 - i) * w[i:i + 1]
        dx_ref[0, pl.ds(r0, rows), :] = dx.astype(dx_ref.dtype)
        return g[:_HALO]

    def later(n, after):                                 # the chunks after the tile's first, last first
        r0 = pl.multiple_of(tile - (n + 1) * rows, rows)
        before = x_ref[0, pl.ds(r0 - halo, halo), :].astype(f32)[halo - _HALO:]
        return chunk(r0, before, after)

    after = lax.fori_loop(0, tile // rows - 1, later, after_ref[...])
    first_tile = pl.program_id(2) == pl.num_programs(2) - 1
    after_ref[...] = chunk(0, _rows_before(prev_ref, first_tile), after)


# -- the calls --------------------------------------------------------------------


def _specs(x, first, width, tile, reverse):
    """Grid (channel block, sequence, token tile) and the blocks of a
    section's arguments by kind; ``reverse`` walks the tiles last to first."""
    bsz, seq, _ = x.shape
    block = _channel_block(first, width)
    nt, c0 = seq // tile, first // block
    halo = 32 // x.dtype.itemsize                        # a sublane tile of x's dtype
    at = (lambda ti: nt - 1 - ti) if reverse else (lambda ti: ti)
    specs = {
        "x": pl.BlockSpec((1, tile, block), lambda ci, bi, ti: (bi, at(ti), c0 + ci)),
        "prev": pl.BlockSpec(
            (1, halo, block),
            lambda ci, bi, ti: (bi, jnp.maximum(at(ti) * (tile // halo) - 1, 0), c0 + ci)),
        "y": pl.BlockSpec((1, tile, block), lambda ci, bi, ti: (bi, at(ti), ci)),
        "w": lambda rows: pl.BlockSpec((rows, block), lambda ci, bi, ti: (0, ci)),
    }
    return (width // block, bsz, nt), block, specs


def _nbytes(*arrays) -> int:
    return sum(a.size * jnp.dtype(a.dtype).itemsize for a in arrays)


# jitted, so that a model's mixers trace and lower each kernel once a shape
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _fwd(x, w, bias, first, width, tile, activation, interpret):
    k = w.shape[0]
    grid, _, specs = _specs(x, first, width, tile, reverse=False)
    out = jax.ShapeDtypeStruct((x.shape[0], x.shape[1], width), x.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, activation=activation),
        grid=grid,
        in_specs=[specs["x"], specs["prev"], specs["w"](k), specs["w"](1)],
        out_specs=specs["y"],
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=(2 * k + 4) * out.size, transcendentals=out.size,
            bytes_accessed=2 * _nbytes(out) + _nbytes(w, bias)),
        interpret=interpret,
        name="causal_conv1d_fwd",
    )(x, x, w, bias)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _bwd(x, w, bias, dy, first, width, tile, activation, interpret):
    k = w.shape[0]
    grid, block, specs = _specs(x, first, width, tile, reverse=True)
    dx = jax.ShapeDtypeStruct(dy.shape, x.dtype)
    sums = jax.ShapeDtypeStruct(((k + 1) * _HALO, width), jnp.float32)
    dx, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, activation=activation),
        grid=grid,
        in_specs=[specs["x"], specs["prev"], specs["w"](k), specs["w"](1), specs["y"]],
        out_specs=[specs["y"], specs["w"]((k + 1) * _HALO)],
        out_shape=[dx, sums],
        scratch_shapes=[pltpu.VMEM((_HALO, block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=(6 * k + 12) * dx.size, transcendentals=dx.size,
            bytes_accessed=3 * _nbytes(dx) + _nbytes(w, bias, sums)),
        interpret=interpret,
        name="causal_conv1d_bwd",
    )(x, x, w, bias, dy.astype(x.dtype))
    sums = sums.reshape(k + 1, _HALO, width).sum(axis=1)
    return dx, sums[:k], sums[k]


# -- one differentiable function over the whole of x ------------------------------


def _sections(w, bias, borders):
    """Each section's taps and bias, its first column in ``x`` and its width."""
    lo = borders[0]
    return [(w[:, a - lo:b - lo], bias[:, a - lo:b - lo], a, b - a) for a, b in zip(borders, borders[1:])]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _conv(x, w, bias, borders, tile, activation, interpret):
    ys = [_fwd(x, *section, tile, activation, interpret) for section in _sections(w, bias, borders)]
    return (x[..., :borders[0]], *ys, x[..., borders[-1]:])


def _conv_fwd(x, w, bias, borders, tile, activation, interpret):
    return _conv(x, w, bias, borders, tile, activation, interpret), (x, w, bias)


def _conv_bwd(borders, tile, activation, interpret, res, cts):
    x, w, bias = res
    parts = [_bwd(x, ws, bs, dy, first, width, tile, activation, interpret)
             for (ws, bs, first, width), dy in zip(_sections(w, bias, borders), cts[1:-1])]
    dxs, dws, dbs = zip(*parts)
    dx = jnp.concatenate([cts[0], *dxs, cts[-1]], axis=-1)
    return dx, jnp.concatenate(dws, axis=-1), jnp.concatenate(dbs, axis=-1)[None]


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv1d(x, w, bias=None, *, borders: Optional[Sequence[int]] = None,
                  activation: Optional[str] = None, tile: Optional[int] = None,
                  interpret: Optional[bool] = None) -> Tuple[jax.Array, ...]:
    """``jnp.split(x, borders, axis=-1)`` with every inner section convolved.

    ``x [B, S, W]``; ``borders`` ascending column borders, multiples of 128
    (default ``(0, W)``); ``w [k, C]`` and ``bias [C]`` (or None) over the
    ``C = borders[-1] - borders[0]`` convolved columns, ``activation`` None or
    ``"silu"``. Returns ``len(borders) + 1`` arrays: ``x``'s columns left of
    ``borders[0]`` as they are, one ``[B, S, width]`` array a section in
    ``x``'s dtype, ``x``'s columns right of ``borders[-1]``. Differentiable
    in ``x``, ``w`` and ``bias``; the gradients of ``w`` and ``bias`` come in
    their dtypes, summed in float32. The shapes must pass :func:`fits`.

    ``tile``: tokens a grid step takes (default: by :func:`fits`' rule).
    ``interpret=None`` selects Pallas interpret mode off the TPU."""
    _, seq, width = x.shape
    borders = (0, width) if borders is None else tuple(int(b) for b in borders)
    k, channels = w.shape
    itemsize = x.dtype.itemsize
    tile = _token_tile(seq, k, itemsize) if tile is None else tile
    if activation not in _ACTIVATIONS:
        raise ValueError(f"activation {activation!r}: one of {_ACTIVATIONS}")
    if (not fits(seq, borders, k, x.dtype) or borders[-1] > width or seq % tile or tile % 16
            or channels != borders[-1] - borders[0]
            or vmem_bytes(tile, CHANNEL_BLOCK, k, itemsize) > VMEM_BUDGET_BYTES):
        raise ValueError(
            f"causal_conv1d cannot take sequences of {seq} tokens in tiles of {tile}, sections "
            f"{borders} of {width} columns ({x.dtype}) and {k} taps over {channels} channels: "
            f"tiles must be whole multiples of 16 tokens, borders ascending multiples of "
            f"{_LANES} inside x that span w's channels, taps at most {MAX_TAPS}, and the "
            f"step's working set must stay within {VMEM_BUDGET_BYTES} B of VMEM")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bias = jnp.zeros((channels,), jnp.float32) if bias is None else bias.astype(jnp.float32)
    return _conv(x, w.astype(jnp.float32), bias[None], borders, tile, activation, interpret)
