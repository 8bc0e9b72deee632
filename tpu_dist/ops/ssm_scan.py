"""Mamba-2's chunked scan as a Pallas kernel pair that walks the chunks in
order and keeps what the einsum form puts through HBM in VMEM.

``nn/nemotron_h.py::ssm_scan`` as XLA einsums materialises every chunk's
state ``[B, nc, G, E, P, N]`` several times over, in float32 and in the
operands' dtype, and autodiff adds the cotangents of both: 19 times the bytes
the mathematics needs at the Nemotron share's shapes (``PERF.md``, PR 34).
Here one grid step takes one chunk of one group: its ``E`` heads share B and
C, so ``C B^T`` is formed once; the ``[chunk, chunk]`` decay lives in vector
registers; the state carried from chunk to chunk is a float32 VMEM scratch
``[E*P, N]`` that never leaves the chip.

Forward, a chunk (``cum`` the in-chunk running sum of ``dt a``, ``<= 0``)::

    y     = (C B^T * L) (dt x)  +  exp(cum) * (C state^T)     L[q,s] = exp(cum_q - cum_s), s <= q
    state <- exp(cum_last) state + (exp(cum_last - cum) dt x)^T B

Backward, two sweeps: the first repeats the state recurrence alone and
writes the state every chunk starts from (float32 ``[B, nc, G, E*P, N]``,
the one large array of the pair); the second walks the chunks in reverse
with the cotangent of the carried state in VMEM and writes ``dx``, ``dB``,
``dC`` (summed over the group's heads), ``d dt`` and ``d cum``. The
residuals are the inputs. The per-token vectors (``dt``, ``cum``) are made
by XLA before the call and their gradients chained by XLA after it.

Layout: x and y are ``[B, S, H*P]``, B and C ``[B, S, G*N]``, as the mixer's
convolution leaves them; a block is a chunk's rows of a group's columns. A
head narrower than 128 lanes shares a lane group with its neighbours and is
separated by lane masks, never by moving lanes (``ops/short_attention.py``).
The per-token vectors come with the tokens down the sublanes, ``[B, G, S,
E]``, and the running exponent also with them along the lanes, ``[B, G, E,
S]``, so that ``cum_q - cum_s`` is a broadcast of one against the other. The
first pads a group's eight heads to 128 lanes in HBM (67 MB an array where 4
would do); transposing an ``[E, chunk]`` tile in the kernel instead cost more
than those reads, which hide under the step's arithmetic (on the chip: 2.07
against 1.89 ms a forward call, PR 34).

Precision is the einsum form's: decays, exponents and the carried state in
float32; the products' operands in ``x``'s dtype with float32 accumulation;
the state is cast to ``x``'s dtype only as an operand.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_dist.ops.short_attention import _NT, _TN, _dot, _head_masks, _merge, _only, lane_group

_LANES = 128
# What one grid step may hold, under the 16 MiB a v5e kernel gets by default.
VMEM_BUDGET_BYTES = 12 * 2**20
# float32 [chunk, E*P]-sized values alive at once in the backward step (x,
# dt x, its decayed copy, dy, y, three cotangents, operand copies).
_LIVE_ROW_TILES = 12
# float32 [chunk, chunk] tiles alive at once (C B^T, its cotangent, a head's
# decay, product and cotangent).
_LIVE_DECAY_TILES = 6


def vmem_bytes(chunk: int, heads: int, head_dim: int, state: int, itemsize: int) -> int:
    """VMEM one grid step of the reverse sweep (the largest of the three)
    needs for a group of ``heads`` heads: x, dy, dx, B, C, dB, dC and the
    chunk's start state, double-buffered by the pipeline, the carried
    cotangent, and the float32 temporaries."""
    width = heads * head_dim
    blocks = 2 * (3 * chunk * width + 4 * chunk * state) * itemsize
    states = (2 + 1) * width * state * 4
    vectors = 2 * 5 * chunk * _LANES * 4
    temps = (_LIVE_ROW_TILES * chunk * width + _LIVE_DECAY_TILES * chunk * chunk
             + 3 * width * state) * 4
    return blocks + states + vectors + temps


def fits(chunk: int, heads: int, head_dim: int, state: int, dtype) -> bool:
    """Whether the pair can take chunks of ``chunk`` tokens for groups of
    ``heads`` heads ``head_dim`` wide with ``state`` state columns: whole
    128-token chunks and 128-lane states, whole heads a lane group, whole
    lane groups a group, and the working set inside the VMEM budget."""
    group = lane_group(head_dim)
    if chunk % _LANES or state % _LANES or group is None or heads % group[1]:
        return False
    return vmem_bytes(chunk, heads, head_dim, state, jnp.dtype(dtype).itemsize) <= VMEM_BUDGET_BYTES


# -- what the three kernels share ------------------------------------------------


def _lane_groups(heads: int, head_dim: int):
    """Static walk over a group's lane groups: (its column slice of x, the
    heads in it)."""
    width, per = lane_group(head_dim)
    for j in range(heads * head_dim // width):
        yield slice(j * width, (j + 1) * width), range(j * per, (j + 1) * per)


def _spread(cols, hs, masks, width):
    """``[Q, E]`` per-head columns -> ``[Q, width]``: each head's column over
    its own lanes of a lane group."""
    out = None
    for h, mask in zip(hs, masks):
        mine = jnp.broadcast_to(cols[:, h:h + 1], (cols.shape[0], width))
        out = _merge(mask, mine, out)
    return out


def _rowsum(mask, x):
    """Sum over a head's own lanes, ``[Q, width] -> [Q, 1]``."""
    return jnp.sum(_only(mask, x), axis=-1, keepdims=True)


def _total(x):
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)  # [1, 1]


def _decay(cum, cum_row, h, causal):
    """A head's ``L[q, s] = exp(cum_q - cum_s)`` for ``s <= q``, else 0.
    Above the diagonal the exponent is positive and may overflow: selected
    away, never multiplied."""
    return jnp.where(causal, jnp.exp(cum[:, h:h + 1] - cum_row[h:h + 1, :]), 0.0)


def _causal(chunk):
    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    return rows >= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)


def _grow_over_chunk(last, h, lanes):
    """``exp(cum_last)`` of head ``h`` as a ``[1, lanes]`` row, which a
    product then broadcasts down the sublanes. The exponential stands between
    the two broadcasts: Mosaic has none in both directions at once, and
    would fold two adjacent ones into that."""
    return jnp.exp(jnp.broadcast_to(last[:, h:h + 1], (1, lanes)))


def _carry(state_ref, hs, head_dim, last, new, col0):
    """``state <- exp(cum_last) state + new`` for the heads ``hs``, whose
    rows of ``new`` start at ``col0``."""
    for h in hs:
        rows = slice(h * head_dim, (h + 1) * head_dim)
        mine = slice(rows.start - col0, rows.stop - col0)
        keep = _grow_over_chunk(last, h, new.shape[1])
        state_ref[rows, :] = keep * state_ref[rows, :] + new[mine, :]


# -- the kernels -----------------------------------------------------------------


def _fwd_kernel(x_ref, dt_ref, cum_ref, cumr_ref, b_ref, c_ref, y_ref, state_ref, *,
                heads, head_dim):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    dtype = x_ref.dtype
    chunk = x_ref.shape[1]
    masks = _head_masks(head_dim)
    dt, cum, cum_row = dt_ref[0, 0], cum_ref[0, 0], cumr_ref[0, 0]
    b, c = b_ref[0], c_ref[0]
    last = cum[chunk - 1:chunk, :]                               # [1, E]
    grow, to_end = jnp.exp(cum), jnp.exp(last - cum)             # [Q, E]
    causal = _causal(chunk)
    cb = _dot(c, b, _NT)                                         # [Q, Q] f32, the group's
    for cols, hs in _lane_groups(heads, head_dim):
        width = cols.stop - cols.start
        xdt = x_ref[0, :, cols].astype(jnp.float32) * _spread(dt, hs, masks, width)
        xdt_op = xdt.astype(dtype)
        y = None
        for h, mask in zip(hs, masks):
            m = (cb * _decay(cum, cum_row, h, causal)).astype(dtype)
            y = _merge(mask, _dot(m, xdt_op), y)
        start = state_ref[cols, :].astype(dtype)                 # [width, N]
        y = y + _dot(c, start, _NT) * _spread(grow, hs, masks, width)
        y_ref[0, :, cols] = y.astype(y_ref.dtype)
        decayed = (xdt * _spread(to_end, hs, masks, width)).astype(dtype)
        _carry(state_ref, hs, head_dim, last, _dot(decayed, b, _TN), cols.start)


def _states_kernel(x_ref, dt_ref, cum_ref, b_ref, start_ref, state_ref, *, heads, head_dim):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    dtype = x_ref.dtype
    chunk = x_ref.shape[1]
    masks = _head_masks(head_dim)
    dt, cum, b = dt_ref[0, 0], cum_ref[0, 0], b_ref[0]
    last = cum[chunk - 1:chunk, :]
    to_end = jnp.exp(last - cum)
    start_ref[0, 0, 0] = state_ref[...]
    for cols, hs in _lane_groups(heads, head_dim):
        width = cols.stop - cols.start
        # in the forward kernel's order of products, so that both sweeps
        # carry the same state bit for bit
        xdt = x_ref[0, :, cols].astype(jnp.float32) * _spread(dt, hs, masks, width)
        decayed = (xdt * _spread(to_end, hs, masks, width)).astype(dtype)
        _carry(state_ref, hs, head_dim, last, _dot(decayed, b, _TN), cols.start)


def _bwd_kernel(x_ref, dt_ref, cum_ref, cumr_ref, b_ref, c_ref, start_ref, dy_ref,
                dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, dstate_ref, *, heads, head_dim):
    """One chunk of the reverse sweep. ``dstate_ref`` carries the cotangent
    of the state at the chunk's end in, at its start out."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    dtype, f32 = x_ref.dtype, jnp.float32
    chunk = x_ref.shape[1]
    masks = _head_masks(head_dim)
    dt, cum, cum_row = dt_ref[0, 0], cum_ref[0, 0], cumr_ref[0, 0]
    b, c = b_ref[0], c_ref[0]
    last = cum[chunk - 1:chunk, :]
    grow, to_end = jnp.exp(cum), jnp.exp(last - cum)
    causal = _causal(chunk)
    is_last = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    head_lane = lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    cb = _dot(c, b, _NT)
    dcb = jnp.zeros((chunk, chunk), f32)
    db = jnp.zeros(b.shape, f32)
    dc = jnp.zeros(c.shape, f32)
    ddt = jnp.zeros(dt.shape, f32)
    dcum = jnp.zeros(dt.shape, f32)
    for cols, hs in _lane_groups(heads, head_dim):
        width = cols.stop - cols.start
        spread = lambda v: _spread(v, hs, masks, width)  # noqa: E731
        x = x_ref[0, :, cols].astype(f32)
        dt_w, grow_w, to_end_w = spread(dt), spread(grow), spread(to_end)
        xdt = x * dt_w
        xdt_op = xdt.astype(dtype)
        decayed = xdt * to_end_w
        dy = dy_ref[0, :, cols]
        start_f = start_ref[0, 0, 0, cols, :]                    # [width, N] f32
        start = start_f.astype(dtype)
        dstate_f = dstate_ref[cols, :]
        dstate = dstate_f.astype(dtype)

        # through the carried state: its update, and the chunk's read of it
        ddecayed = _dot(b, dstate, _NT)                          # [Q, width]
        db += _dot(decayed.astype(dtype), dstate)
        dread = (dy.astype(f32) * grow_w).astype(dtype)          # cotangent of C state^T
        dc += _dot(dread, start)
        dstart = _dot(dread, c, _TN)                             # [width, N]

        # inside the chunk, a head at a time
        y = dxdt = None
        for h, mask in zip(hs, masks):
            decay = _decay(cum, cum_row, h, causal)
            m = (cb * decay).astype(dtype)
            y = _merge(mask, _dot(m, xdt_op), y)
            dcb += _dot(_only(mask, dy), xdt_op, _NT) * decay
            dxdt = _merge(mask, _dot(m, dy, _TN), dxdt)
        y = y + _dot(c, start, _NT) * grow_w                      # the forward's y, float32
        # d cum: + over a row of dL * L, - over a column. Both sums must be
        # of one matrix, or their difference does not cancel along the chunk:
        # the column's side takes dt x as the product saw it, rounded
        at_end = ddecayed * decayed
        through_cum = dy.astype(f32) * y - xdt_op.astype(f32) * dxdt - at_end
        to_last = jnp.sum(at_end, axis=0, keepdims=True)          # [1, width]
        dxdt = dxdt + ddecayed * to_end_w
        dx_ref[0, :, cols] = (dxdt * dt_w).astype(dx_ref.dtype)
        through_x = x * dxdt                                      # d dt through dt x alone
        for h, mask in zip(hs, masks):
            rows = slice(h * head_dim - cols.start, (h + 1) * head_dim - cols.start)
            grow_last = jnp.exp(last[:, h:h + 1])                # [1, 1]
            dlast = (grow_last * _total(dstate_f[rows] * start_f[rows])
                     + jnp.sum(_only(mask, to_last), axis=1, keepdims=True))
            mine = head_lane == h
            ddt = jnp.where(mine, _rowsum(mask, through_x), ddt)
            dcum = jnp.where(
                mine, _rowsum(mask, through_cum) + jnp.where(is_last, dlast, 0.0), dcum)
        _carry(dstate_ref, hs, head_dim, last, dstart, cols.start)
    dcb = dcb.astype(dtype)
    db_ref[0] = (db + _dot(dcb, c, _TN)).astype(db_ref.dtype)
    dc_ref[0] = (dc + _dot(dcb, b)).astype(dc_ref.dtype)
    ddt_ref[0, 0] = ddt
    dcum_ref[0, 0] = dcum


# -- the calls -------------------------------------------------------------------


def _nbytes(t) -> int:
    return math.prod(t.shape) * jnp.dtype(t.dtype).itemsize


def _call(kernel, name, dims, reverse, args, specs, out_shapes, out_specs, *,
          tile_matmuls, interpret):
    """Grid (batch, group, chunk), the chunk axis sequential; ``reverse``
    walks it from the last chunk to the first. ``specs`` name each argument's
    block by kind (below). ``tile_matmuls``: ``[chunk, width, N]``-sized
    products a step takes, for the cost XLA's scheduler sees."""
    bsz, nc, chunk, g, heads, head_dim, n = dims
    width = heads * head_dim
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    blocks = {
        "x": pl.BlockSpec((1, chunk, width), lambda bi, gi, ci: (bi, at(ci), gi)),
        "bc": pl.BlockSpec((1, chunk, n), lambda bi, gi, ci: (bi, at(ci), gi)),
        "col": pl.BlockSpec((1, 1, chunk, heads), lambda bi, gi, ci: (bi, gi, at(ci), 0)),
        "row": pl.BlockSpec((1, 1, heads, chunk), lambda bi, gi, ci: (bi, gi, 0, at(ci))),
        "state": pl.BlockSpec((1, 1, 1, width, n), lambda bi, gi, ci: (bi, at(ci), gi, 0, 0)),
    }
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, head_dim=head_dim),
        grid=(bsz, g, nc),
        in_specs=[blocks[s] for s in specs],
        out_specs=[blocks[s] for s in out_specs],
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((width, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=tile_matmuls * 2 * bsz * g * nc * chunk * width * n,
            transcendentals=bsz * g * nc * heads * chunk * (chunk + 2),
            bytes_accessed=sum(map(_nbytes, (*args, *out_shapes))),
        ),
        interpret=interpret,
        name=name,
    )(*args)


def _layouts(x, dt, cum, b, c, chunk):
    """The kernels' views of the arguments and the sizes they share."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    e = h // g
    col = lambda v: v.reshape(bsz, s, g, e).transpose(0, 2, 1, 3)  # noqa: E731
    dims = (bsz, s // chunk, chunk, g, e, p, n)
    views = dict(
        x=x.reshape(bsz, s, h * p), b=b.reshape(bsz, s, g * n), c=c.reshape(bsz, s, g * n),
        dt=col(dt), cum=col(cum), cum_row=cum.reshape(bsz, s, g, e).transpose(0, 2, 3, 1),
    )
    return dims, views


# jitted, so that a model's mixers trace and lower each kernel once
# (ops/short_attention.py: twelve sites, twelve lowerings, +29% set-up)
@functools.partial(jax.jit, static_argnums=(5, 6))
def _fwd(x, dt, cum, b, c, chunk, interpret):
    dims, v = _layouts(x, dt, cum, b, c, chunk)
    y = _call(
        _fwd_kernel, "ssm_scan_fwd", dims, False,
        (v["x"], v["dt"], v["cum"], v["cum_row"], v["b"], v["c"]),
        ("x", "col", "col", "row", "bc", "bc"),
        [jax.ShapeDtypeStruct(v["x"].shape, x.dtype)], ("x",),
        tile_matmuls=3, interpret=interpret,
    )[0]
    return y.reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _bwd(x, dt, cum, b, c, dy, chunk, interpret):
    dims, v = _layouts(x, dt, cum, b, c, chunk)
    bsz, nc, _, g, e, p, n = dims
    s = nc * chunk
    start = _call(
        _states_kernel, "ssm_scan_states", dims, False,
        (v["x"], v["dt"], v["cum"], v["b"]), ("x", "col", "col", "bc"),
        [jax.ShapeDtypeStruct((bsz, nc, g, e * p, n), jnp.float32)], ("state",),
        tile_matmuls=1, interpret=interpret,
    )[0]
    vec = jax.ShapeDtypeStruct(v["dt"].shape, jnp.float32)
    dx, ddt, dcum, db, dc = _call(
        _bwd_kernel, "ssm_scan_bwd", dims, True,
        (v["x"], v["dt"], v["cum"], v["cum_row"], v["b"], v["c"], start,
         dy.astype(x.dtype).reshape(v["x"].shape)),
        ("x", "col", "col", "row", "bc", "bc", "state", "x"),
        [jax.ShapeDtypeStruct(v["x"].shape, x.dtype), vec, vec,
         jax.ShapeDtypeStruct(v["b"].shape, b.dtype), jax.ShapeDtypeStruct(v["c"].shape, c.dtype)],
        ("x", "col", "col", "bc", "bc"),
        tile_matmuls=10, interpret=interpret,
    )
    flat = lambda t: t.transpose(0, 2, 1, 3).reshape(bsz, s, g * e)  # noqa: E731
    return dx.reshape(x.shape), flat(ddt), flat(dcum), db.reshape(b.shape), dc.reshape(c.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, dt, cum, b, c, chunk, interpret):
    return _fwd(x, dt, cum, b, c, chunk, interpret)


def _scan_fwd(x, dt, cum, b, c, chunk, interpret):
    return _fwd(x, dt, cum, b, c, chunk, interpret), (x, dt, cum, b, c)


def _scan_bwd(chunk, interpret, res, dy):
    return _bwd(*res, dy, chunk, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssm_scan(x, dt, a, b, c, chunk: int, *, interpret: bool | None = None):
    """``nn/nemotron_h.py::ssm_scan``'s contract with float32 decays and
    state: ``x [B,S,H,P]``, ``dt [B,S,H]`` (float32), ``a [H]`` (float32,
    negative), ``b``/``c [B,S,G,N]`` -> ``y [B,S,H,P]`` in ``x``'s dtype,
    differentiable in all five. The shapes must pass :func:`fits`.

    ``interpret=None`` selects Pallas interpret mode off the TPU."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if s % chunk:
        raise ValueError(f"sequence {s} is not whole chunks of {chunk} tokens")
    if h % g or not fits(chunk, h // g, p, n, x.dtype):
        raise ValueError(
            f"ssm_scan cannot take chunk={chunk}, {h} heads of {p} in {g} groups, state {n} "
            f"({x.dtype}): chunk and state must be multiples of {_LANES}, heads must fill "
            f"whole 128-lane groups and the step's working set must stay within "
            f"{VMEM_BUDGET_BYTES} B of VMEM"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dt = dt.astype(jnp.float32)
    steps = (dt * a.astype(jnp.float32)).reshape(bsz, s // chunk, chunk, h)
    cum = jnp.cumsum(steps, axis=2).reshape(bsz, s, h)           # in-chunk, <= 0
    return _scan(x, dt, cum, b, c, chunk, interpret)
