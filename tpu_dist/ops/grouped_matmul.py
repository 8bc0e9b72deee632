"""The dropless experts' grouped product as a Pallas kernel pair over the
sorted tiles, each kernel told every tile's expert by scalar prefetch.

``parallel/expert.py::grouped_matmul`` as a loop of XLA dots puts every
tile's weight-gradient product through HBM as a float32 ``[a, b]`` array and
reads and writes its expert's float32 slab a tile, and stacks every tile's
output through a ``dynamic_update_slice`` (``PERF.md``, PR 36). The rows are
sorted by expert, so an expert's tiles are consecutive, and here:

* :func:`gmm` (the product, and ``dx`` with the expert matrix read
  transposed) walks (``b`` block, tile, ``a`` block): the expert's matrix is
  read in place through ``tile_expert[t]`` and stays in VMEM while the tiles
  are that expert's, the float32 accumulator is a VMEM scratch, and a tile's
  output is cast once and written once;
* :func:`tgmm` (the weight gradient) walks (``a`` block, ``b`` block, tile):
  the output block is ``tile_expert[t]``'s, so consecutive tiles of one
  expert add into one float32 VMEM accumulator, which is cast and written
  when the expert changes: a slab once, not once a tile. The output starts
  as zeros (aliased in), which is what an expert with no tile keeps.

Tiles from ``n_live`` on are dead: their index maps stay on the last live
tile's blocks, so nothing is copied in for them (the trick of
``ops/flash_attention.py``'s skipped causal tiles); :func:`gmm` writes zeros
for them and :func:`tgmm` adds nothing, whatever their rows hold.

An ``[experts, p, q]`` matrix whose ``q`` is no multiple of 128 while ``p``
is one (the Nemotron share's ``[8, 2688, 1856]``) lives on the TPU with ``p``
minor; both entries take its transpose read transposed, the same bytes, and
spare XLA a copy of the weights, their gradient and the optimizer's moments.

Precision is the loop's: operands in the input dtype, float32 accumulation,
the weight gradient summed in float32 over all of an expert's rows and cast
once. Only the order of that float32 sum differs.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_dist.ops.short_attention import _NT, _TN, _dot

_LANES = 128
# What one call may ask of the v5e's 128 MiB of VMEM (a kernel gets 16 MiB
# unless it says otherwise: ``vmem_limit_bytes`` below).
VMEM_BUDGET_BYTES = 96 * 2**20
# The most elements a block takes of a width (PERF.md, PR 36, the block-size
# table): the LFM2 share's 2048 and 1536 whole, the Nemotron share's 2688 in
# three blocks of 896 beside its 1856 whole.
BLOCK = 2048


def _block(dim: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``BLOCK``; a ``dim`` that is no multiple of 128 is taken whole."""
    if dim % _LANES:
        return dim
    return max(d for d in range(_LANES, min(dim, BLOCK) + 1, _LANES) if dim % d == 0)


def _gmm_vmem(rows: int, tk: int, tn: int, itemsize: int) -> int:
    """x, the expert's block and the output double-buffered, the float32
    accumulator and the product before it is added."""
    return 2 * (rows * tk + tk * tn + rows * tn) * itemsize + 2 * rows * tn * 4


def _tgmm_vmem(rows: int, tp: int, tq: int, itemsize: int) -> int:
    return 2 * (rows * (tp + tq) + tp * tq) * itemsize + 2 * tp * tq * 4


def vmem_bytes(rows: int, a: int, b: int, itemsize: int) -> int:
    """The most VMEM one grid step of the calls needs (the product, its
    transposed form, the weight gradient) at the blocks :func:`_block` gives
    ``a`` and ``b``."""
    ta, tb = _block(a), _block(b)
    return max(_gmm_vmem(rows, ta, tb, itemsize), _gmm_vmem(rows, tb, ta, itemsize),
               _tgmm_vmem(rows, ta, tb, itemsize))


def fits(rows: int, a: int, b: int, dtype) -> bool:
    """Whether the pair can take tiles of ``rows`` rows against ``[a, b]``
    expert matrices: whole 128-row tiles (a tile's rows are the weight
    gradient's contraction), ``a`` and ``b`` in blocks of whole 128-lane
    groups or, where they are no multiple of 128, whole (then wider than one
    lane group and whole sublane groups of either dtype: 1856 = 14.5 x 128
    is the case), and the working set inside the budget."""
    if rows % _LANES or any(d % _LANES and (d < _LANES or d % 16) for d in (a, b)):
        return False
    return vmem_bytes(rows, a, b, jnp.dtype(dtype).itemsize) <= VMEM_BUDGET_BYTES


def _held_tile(t, n_live):
    """The tile whose blocks grid step ``t`` holds: a dead step stays on the
    last live tile (tile 0 where none is live) and copies nothing in."""
    return jnp.minimum(t, jnp.maximum(n_live[0] - 1, 0))


# -- the kernels -----------------------------------------------------------------


def _gmm_kernel(te_ref, nl_ref, x_ref, w_ref, o_ref, acc_ref, *, transpose):
    del te_ref
    t, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < nl_ref[0])
    def _():
        x, w = x_ref[...], w_ref[...]
        acc_ref[...] += _dot(x, w, _NT) if transpose else _dot(x, w)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tgmm_kernel(te_ref, nl_ref, lhs_ref, rhs_ref, zeros_ref, o_ref, acc_ref):
    del zeros_ref  # aliased to the output: what an expert with no tile keeps
    t, tiles, n_live = pl.program_id(2), pl.num_programs(2), nl_ref[0]
    e = te_ref[t]
    first = jnp.logical_or(t == 0, te_ref[jnp.maximum(t - 1, 0)] != e)
    last = jnp.logical_or(t == n_live - 1, te_ref[jnp.minimum(t + 1, tiles - 1)] != e)
    live = t < n_live

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _():
        acc_ref[...] += _dot(lhs_ref[...], rhs_ref[...], _TN)

    # with no live tile the block the dead steps hold still goes out: as zeros
    @pl.when(jnp.logical_or(jnp.logical_and(live, last), jnp.logical_and(n_live == 0, t == 0)))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# -- the calls -------------------------------------------------------------------


def _nbytes(t) -> int:
    return math.prod(t.shape) * jnp.dtype(t.dtype).itemsize


def _call(kernel, name, grid, in_specs, out_spec, out, acc_shape, vmem, args, *, interpret, **kw):
    """One kernel over ``grid`` (its last axis sequential) with the tiles'
    experts and the live count prefetched as scalars, a float32 accumulator
    in VMEM, and the VMEM limit its blocks need."""
    tile_expert, n_live, *arrays = args
    flops = 2 * math.prod(arrays[0].shape) * out.shape[-1]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs, out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32)],
        ),
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=min(vmem + vmem // 4 + 4 * 2**20, 120 * 2**20),
        ),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=0, bytes_accessed=sum(map(_nbytes, (*arrays[:2], out)))),
        interpret=interpret,
        name=name,
        **kw,
    )(tile_expert.astype(jnp.int32), jnp.asarray(n_live, jnp.int32).reshape(1), *arrays)


# jitted, so that a model's expert layers trace and lower each kernel once a
# shape (ops/short_attention.py: twelve sites, twelve lowerings, +29% set-up);
# the blocks are static arguments, chosen outside
@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _gmm(x, w, tile_expert, n_live, transpose, blocks, interpret):
    tiles, rows, a = x.shape
    b = w.shape[1 if transpose else 2]
    tk, tn = blocks
    nk = a // tk

    def k_of(t, k, nl):  # a dead step stays on the last live step's blocks
        return jnp.where(t < nl[0], k, nk - 1)

    def w_index(j, t, k, te, nl):
        e, k = te[_held_tile(t, nl)], k_of(t, k, nl)
        return (e, j, k) if transpose else (e, k, j)

    return _call(
        functools.partial(_gmm_kernel, transpose=transpose), "moe_gmm", (b // tn, tiles, nk),
        [pl.BlockSpec((rows, tk), lambda j, t, k, te, nl: (_held_tile(t, nl), k_of(t, k, nl))),
         pl.BlockSpec((None, tn, tk) if transpose else (None, tk, tn), w_index)],
        pl.BlockSpec((rows, tn), lambda j, t, k, te, nl: (t, j)),
        jax.ShapeDtypeStruct((tiles * rows, b), x.dtype), (rows, tn),
        _gmm_vmem(rows, tk, tn, x.dtype.itemsize),
        (tile_expert, n_live, x.reshape(tiles * rows, a), w), interpret=interpret,
    ).reshape(tiles, rows, b)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _tgmm(lhs, rhs, tile_expert, n_live, experts, out_dtype, blocks, interpret):
    tiles, rows, p = lhs.shape
    q = rhs.shape[2]
    tp, tq = blocks
    out = jax.ShapeDtypeStruct((experts, p, q), out_dtype)
    return _call(
        _tgmm_kernel, "moe_tgmm", (p // tp, q // tq, tiles),
        [pl.BlockSpec((rows, tp), lambda i, j, t, te, nl: (_held_tile(t, nl), i)),
         pl.BlockSpec((rows, tq), lambda i, j, t, te, nl: (_held_tile(t, nl), j)),
         pl.BlockSpec(memory_space=pl.ANY)],
        pl.BlockSpec((None, tp, tq), lambda i, j, t, te, nl: (te[_held_tile(t, nl)], i, j)),
        out, (tp, tq), _tgmm_vmem(rows, tp, tq, lhs.dtype.itemsize),
        (tile_expert, n_live, lhs.reshape(tiles * rows, p), rhs.reshape(tiles * rows, q),
         jnp.zeros(out.shape, out.dtype)),
        interpret=interpret, input_output_aliases={4: 0},
    )


def _refuse(rows, a, b, dtype):
    if not fits(rows, a, b, dtype):
        raise ValueError(
            f"the grouped product's kernels cannot take tiles of {rows} rows against "
            f"[{a}, {b}] matrices ({jnp.dtype(dtype).name}): rows must be a multiple of "
            f"{_LANES}, widths multiples of {_LANES} or wider, multiples of 16 and taken whole, "
            f"and a step's working set must stay within {VMEM_BUDGET_BYTES} B of VMEM"
        )


def _kept_transposed(p: int, q: int) -> bool:
    """Whether XLA keeps an ``[experts, p, q]`` array on the TPU with ``p``
    as its minor dimension: where ``q`` is no multiple of 128 and ``p`` is
    one, that pads nothing. A kernel's operand is row-major, so reading such
    a matrix as stored would cost a copy of it, of its gradient and of the
    optimizer's moments a step (PERF.md, PR 36: 18 copies of ``f32[8, 2688,
    1856]``, 9.2 ms of the Nemotron share's step); its transpose read
    transposed is the same bytes."""
    return q % _LANES != 0 and p % _LANES == 0


def _interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def gmm(x, w, tile_expert, n_live, transpose: bool = False, *, interpret: bool | None = None):
    """``x [tiles, rows, a]`` times, tile by tile, ``w[tile_expert[t]]`` of
    ``w [experts, a, b]`` (``[experts, b, a]``, read transposed, with
    ``transpose``) -> ``[tiles, rows, b]`` in ``x``'s dtype; tiles from
    ``n_live`` on give zeros. The shapes must pass :func:`fits`.

    ``interpret=None`` selects Pallas interpret mode off the TPU."""
    a, b = (w.shape[2], w.shape[1]) if transpose else w.shape[1:]
    _refuse(x.shape[1], a, b, x.dtype)
    if _kept_transposed(*w.shape[1:]):
        w, transpose = w.swapaxes(1, 2), not transpose
    return _gmm(x, w, tile_expert, n_live, transpose, (_block(a), _block(b)), _interpret(interpret))


def tgmm(x, dy, tile_expert, n_live, experts: int, out_dtype, transpose: bool = False, *,
         interpret: bool | None = None):
    """The weight gradient of :func:`gmm`: for every expert the sum over its
    live tiles of ``x_t^T dy_t`` -> ``[experts, a, b]`` (of ``dy_t^T x_t`` ->
    ``[experts, b, a]`` with ``transpose``), summed in float32 and cast once
    to ``out_dtype``; an expert with no live tile gets zeros."""
    _refuse(x.shape[1], x.shape[2], dy.shape[2], x.dtype)
    lhs, rhs = (dy, x) if transpose else (x, dy)
    swap = _kept_transposed(lhs.shape[2], rhs.shape[2])
    if swap:
        lhs, rhs = rhs, lhs
    blocks = (_block(lhs.shape[2]), _block(rhs.shape[2]))
    dw = _tgmm(lhs, rhs, tile_expert, n_live, experts, jnp.dtype(out_dtype), blocks,
               _interpret(interpret))
    return dw.swapaxes(1, 2) if swap else dw
