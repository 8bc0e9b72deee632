"""The dropless experts' rows back in token order, without a scatter: a Pallas
kernel that builds each block of tokens from the held experts' sorted runs of
rows and writes the block once.

``parallel/expert.py::dropless_experts`` sorts the (token, slot) pairs by held
expert with a stable sort over token-major pair ids, and ``top_k`` picks
distinct experts, so inside one expert's rows of the buffer the tokens rise
strictly. For a block of ``tb`` consecutive tokens each held expert's rows are
therefore one contiguous run of at most ``tb`` buffer rows, and ``runs
[blocks, count, 2]`` (each run's first and last-plus-one buffer row, computed
by XLA before the call) says where. As XLA forms, the combine is
``zeros(f32[T, d]).at[token].add(y)`` and the dispatch gather ``x[token]``'s
transpose the same scatter: each a read-modify-write of a float32 ``[T, d]``
array in HBM, 2.3-4.2 ms a call on a v5e chip (``PERF.md``). Here one grid
step owns one block of tokens:

* it reads the runs from HBM in chunks of ``CHUNK`` rows aligned to ``CHUNK``
  (double-buffered DMAs; a chunk list per block is made by XLA from ``runs``),
* places each run's rows at their tokens with a 0/1 product on the MXU (exact:
  a token holds at most one row of an expert, so every output element is one
  row's value or zero), rows of the chunk outside the run masked out,
* multiplies by the row's ``scale`` in float32 after the product, a token's
  scale picked out of the chunk's by the same 0/1 mask,
* sums the experts in a float32 VMEM accumulator and writes the block once,
  cast once, as NaN where ``over > 0``. A token no held expert took gets zeros.

Rows outside every run (padding, dead tiles, pairs past the buffer's capacity)
are read only where they share an aligned chunk with a run's rows, and are then
masked out of the product: they must be finite (a 0 times NaN on the MXU is
NaN), which the expert layer's buffer is, its padding rows being products of
zero rows.

Precision: the products of the combine are ``f32(y) * scale`` in float32 and
the sum is float32, as the scatter's; only the order of the sum differs. The
dispatch's backward (no scale) sums in float32 and casts once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
CHUNK = 128          # buffer rows a read takes: one contraction pass of the MXU
TOKEN_BLOCK = 512    # tokens a grid step owns
_SLICE = 512         # the most lanes of the width one product takes
# What one call may ask of the v5e's 128 MiB of VMEM.
VMEM_BUDGET_BYTES = 96 * 2**20


def _slice(width: int) -> int:
    """The largest multiple of 128 that divides ``width`` and is at most ``_SLICE``."""
    return max(s for s in range(_LANES, min(width, _SLICE) + 1, _LANES) if width % s == 0)


def vmem_bytes(width: int, rows: int, itemsize: int) -> int:
    """The output block double-buffered, the float32 accumulator, the two
    read buffers, the token and scale rows of the whole buffer
    (double-buffered), and one product before it is added."""
    return (2 * TOKEN_BLOCK * width * itemsize + TOKEN_BLOCK * width * 4
            + 2 * CHUNK * width * itemsize + 2 * 2 * rows * 4
            + 2 * TOKEN_BLOCK * _slice(width) * 4)


def fits(tokens: int, width: int, rows: int, dtype) -> bool:
    """Whether the kernel can build ``tokens`` tokens of ``width`` from a
    buffer of ``rows`` rows: whole blocks of ``TOKEN_BLOCK`` tokens, the width
    in 128-lane blocks, whole chunks of buffer rows, 2- or 4-byte rows, and
    the working set inside the VMEM budget."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in (2, 4) or tokens % TOKEN_BLOCK or width % _LANES or rows % CHUNK:
        return False
    return vmem_bytes(width, rows, itemsize) <= VMEM_BUDGET_BYTES


def _chunk_table(runs):
    """Each block's chunks, runs in expert order: ``[blocks, most, 2]`` (the
    chunk, its expert), the number each block has, and ``most``, the static
    bound (a run of at most ``tb`` rows spans at most ``tb / CHUNK + 1``
    aligned chunks)."""
    blocks, count, _ = runs.shape
    most = count * (TOKEN_BLOCK // CHUNK + 1)
    lo, hi = runs[..., 0], runs[..., 1]
    first = lo // CHUNK
    n = jnp.where(hi > lo, (hi - 1) // CHUNK - first + 1, 0)
    ends = jnp.cumsum(n, axis=1)
    i = jnp.arange(most)
    e = jnp.minimum((i[None, :, None] >= ends[:, None, :]).sum(-1), count - 1)
    chunk = (jnp.take_along_axis(first, e, 1) + i
             - jnp.take_along_axis(ends - n, e, 1))
    return jnp.stack([chunk, e], -1).astype(jnp.int32), ends[:, -1].astype(jnp.int32), most


def _combine_kernel(runs_ref, table_ref, n_ref, over_ref, tok_ref, *refs, count, most, scaled):
    if scaled:
        scale_ref, src_ref, o_ref, acc_ref, buf_ref, sem_ref = refs
    else:
        src_ref, o_ref, acc_ref, buf_ref, sem_ref = refs
    tb, width = acc_ref.shape
    ws = _slice(width)
    b = pl.program_id(0)
    n = n_ref[b]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def entry(i, field):
        return table_ref[(b * most + i) * 2 + field]

    def copy(i, slot):
        start = pl.multiple_of(entry(i, 0) * CHUNK, CHUNK)
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(start, CHUNK)], buf_ref.at[slot], sem_ref.at[slot])

    @pl.when(n > 0)
    def _():
        copy(0, 0).start()

    def step(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n)
        def _():
            copy(i + 1, 1 - slot).start()

        copy(i, slot).wait()
        c, e = entry(i, 0), entry(i, 1)
        base = (b * count + e) * 2
        lo, hi = runs_ref[base] - c * CHUNK, runs_ref[base + 1] - c * CHUNK
        lane = lax.broadcasted_iota(jnp.int32, (1, CHUNK), 1)
        at = tok_ref[pl.ds(c, 1), :] - b * tb                      # [1, CHUNK]
        place = ((lax.broadcasted_iota(jnp.int32, (tb, CHUNK), 0) == at)
                 & (lane >= lo) & (lane < hi))                      # [tb, CHUNK]
        onehot = place.astype(buf_ref.dtype)
        precision = lax.Precision.HIGHEST if buf_ref.dtype == jnp.float32 else None
        if scaled:                                                  # a token's scale, exact
            col = jnp.sum(jnp.where(place, scale_ref[pl.ds(c, 1), :], 0.0), axis=1, keepdims=True)
        for j in range(0, width, ws):
            part = lax.dot_general(onehot, buf_ref[slot, :, j:j + ws], (((1,), (0,)), ((), ())),
                                   precision=precision, preferred_element_type=jnp.float32)
            acc_ref[:, j:j + ws] += part * col if scaled else part
        return carry

    lax.fori_loop(0, n, step, 0)
    bad = over_ref[0] > 0
    for j in range(0, width, ws):
        o_ref[:, j:j + ws] = jnp.where(bad, jnp.nan, acc_ref[:, j:j + ws]).astype(o_ref.dtype)


def _nbytes(t) -> int:
    return math.prod(t.shape) * jnp.dtype(t.dtype).itemsize


# jitted, so that a model's expert layers trace and lower the kernel once a shape
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _call(src, token, scale, runs, over, n_tokens, out_dtype, interpret):
    rows, width = src.shape
    count = runs.shape[1]
    table, n_chunks, most = _chunk_table(runs)
    whole = pl.BlockSpec((rows // CHUNK, CHUNK), lambda b, *_: (0, 0))
    scaled = scale is not None
    arrays = [token.astype(jnp.int32).reshape(rows // CHUNK, CHUNK)]
    if scaled:
        arrays.append(scale.astype(jnp.float32).reshape(rows // CHUNK, CHUNK))
    out = jax.ShapeDtypeStruct((n_tokens, width), out_dtype)
    vmem = vmem_bytes(width, rows, src.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_combine_kernel, count=count, most=most, scaled=scaled),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n_tokens // TOKEN_BLOCK,),
            in_specs=[whole] * len(arrays) + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((TOKEN_BLOCK, width), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((TOKEN_BLOCK, width), jnp.float32),
                            pltpu.VMEM((2, CHUNK, width), src.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(vmem + vmem // 4 + 4 * 2**20, 120 * 2**20),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * TOKEN_BLOCK * width, transcendentals=0,
            bytes_accessed=_nbytes(src) + _nbytes(out)),
        interpret=interpret,
        name="moe_combine",
    )(runs.reshape(-1).astype(jnp.int32), table.reshape(-1), n_chunks,
      jnp.asarray(over, jnp.int32).reshape(1), *arrays, src)


def tokens_from_runs(src, token, scale, runs, over, n_tokens: int, out_dtype, *,
                     interpret: bool | None = None):
    """``out [n_tokens, d]``: for every token the float32 sum of ``scale[r] *
    src[r]`` over the buffer rows ``r`` of ``src [R, d]`` that ``runs`` names
    and whose ``token[r]`` it is (``scale`` None: of ``src[r]``), cast once to
    ``out_dtype``; all NaN where ``over > 0``.

    ``runs [n_tokens / TOKEN_BLOCK, count, 2]``: for each block of
    ``TOKEN_BLOCK`` tokens and each expert, the first and last-plus-one buffer
    row of the expert's rows whose tokens lie in the block (equal where there
    are none); inside a run the tokens rise strictly. The shapes must pass
    :func:`fits`. ``interpret=None`` selects Pallas interpret mode off the TPU."""
    rows, width = src.shape
    if not fits(n_tokens, width, rows, src.dtype) or runs.shape[0] * TOKEN_BLOCK != n_tokens:
        raise ValueError(
            f"the combine kernel cannot build {n_tokens} tokens of {width} from {rows} rows "
            f"({jnp.dtype(src.dtype).name}) with runs {runs.shape}: tokens must be whole blocks of "
            f"{TOKEN_BLOCK} with a run table a block, the width a multiple of {_LANES}, rows a "
            f"multiple of {CHUNK}, and the working set within {VMEM_BUDGET_BYTES} B of VMEM")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _call(src, token, scale, runs, over, n_tokens, jnp.dtype(out_dtype), interpret)
