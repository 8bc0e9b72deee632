"""Whole-sequence Pallas attention for sequences whose score tile fits VMEM.

At ViT lengths (S=196) XLA's attention is six separate ops: the ``[B,H,S,S]``
scores go to HBM in bf16, come back as f32 ``exp`` (kept as the backward's
residual), and q, k, v are each copied out of the fused projection into a
layout of their own. None of that is needed when one head's whole ``[S,S]``
f32 tile is ~150 KB: this kernel takes one image per grid step, every head
inside, reads the qkv projection's output where the matmul left it and writes
``[B,S,H*D]`` where the output projection reads it. No online softmax, no
``[.,S,S]`` array in HBM, no relayout. ``ops/flash_attention.py`` (tiled,
``[BH,S,D]``) stays the long-sequence kernel.

Layout: ``qkv`` is ``[B, S, 3*H*D]`` with columns **q|k|v-major**, then
head, then width (``nn/attention.py::qkv_major`` permutes a head-major
projection's weights at trace time), so q, k, v are lane-aligned slabs. With
``D < 128`` several heads share a 128-lane group. They are separated without
moving a lane: zeroing the other heads' lanes of q makes a 128-deep
contraction equal the head's own ``q.k``; ``p @ v_group`` is right in the
head's own lanes; a lane select merges the heads of a group.

Precision: q, k, v and p enter the MXU in the input dtype (bf16 in training)
with f32 accumulation; scale, max, exp, sum and the division are f32. The
forward saves its output and one f32 log-sum-exp per row (``[B,S,H]``); the
backward recomputes p from q, k and the log-sum-exp, forms dv, dp, ds, dq, dk
in VMEM and writes ``d(qkv)`` packed in the same column order.
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
# What one grid step may hold: under the 16 MiB a v5e kernel gets by default,
# with room for what the estimate below does not see (spills, semaphores).
VMEM_BUDGET_BYTES = 12 * 2**20
# f32 [S,S] tiles alive at once in the backward (s/p, dp, ds and their bf16
# copies, two heads in flight where the compiler overlaps them).
_LIVE_SCORE_TILES = 8

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def lane_group(head_dim: int):
    """(lanes a group of heads spans, heads in it), or None where heads
    would straddle a 128-lane boundary."""
    if head_dim % _LANES == 0:
        return head_dim, 1
    if _LANES % head_dim == 0:
        return _LANES, _LANES // head_dim
    return None


def vmem_bytes(seq: int, heads: int, head_dim: int, itemsize: int) -> int:
    """VMEM one grid step of the backward kernel (the larger one) needs:
    qkv and d(qkv), o and d(o), double-buffered by the pipeline, plus the
    score-sized f32 temporaries."""
    rows = _round_up(seq, 32 // itemsize)
    blocks = 2 * (2 * 3 + 2) * rows * heads * head_dim * itemsize
    lse = 2 * _round_up(seq, 8) * _round_up(heads, _LANES) * 4
    scores = _LIVE_SCORE_TILES * _round_up(seq, 8) * _round_up(seq, _LANES) * 4
    return blocks + lse + scores


def fits(seq: int, heads: int, head_dim: int, dtype) -> bool:
    """Whether ``short_attention`` can take ``[., seq, 3*heads*head_dim]``:
    whole heads per lane group, lane-aligned q/k/v slabs, and the working
    set inside the VMEM budget."""
    group = lane_group(head_dim)
    if group is None or heads % group[1]:
        return False
    return vmem_bytes(seq, heads, head_dim, jnp.dtype(dtype).itemsize) <= VMEM_BUDGET_BYTES


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _groups(heads: int, head_dim: int):
    """Static walk over the lane groups: (group index, its column slice
    within a q, k or v slab)."""
    width, _ = lane_group(head_dim)
    for g in range(heads * head_dim // width):
        yield g, slice(g * width, (g + 1) * width)


def _head_masks(head_dim: int):
    """One ``[1, width]`` lane mask per head of a group (None where a group
    is one head)."""
    width, per_group = lane_group(head_dim)
    if per_group == 1:
        return [None]
    head_of_lane = lax.broadcasted_iota(jnp.int32, (1, width), 1) // head_dim
    return [head_of_lane == j for j in range(per_group)]


def _only(mask, x):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _merge(mask, mine, others):
    return mine if others is None or mask is None else jnp.where(mask, mine, others)


def _shift(ref_slice: slice, by: int) -> slice:
    return slice(ref_slice.start + by, ref_slice.stop + by)


def _short_attn_fwd_kernel(qkv_ref, o_ref, lse_ref, *, heads, head_dim, scale):
    hd = heads * head_dim
    masks = _head_masks(head_dim)
    head_lane = lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    lse = jnp.zeros(lse_ref.shape[1:], jnp.float32)
    for g, cols in _groups(heads, head_dim):
        q = qkv_ref[0, :, cols]
        k = qkv_ref[0, :, _shift(cols, hd)]
        v = qkv_ref[0, :, _shift(cols, 2 * hd)]
        o = None
        for j, mask in enumerate(masks):
            s = _dot(_only(mask, q), k, _NT) * scale          # [S, S] f32
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            o_j = _dot(p.astype(v.dtype), v) * (1.0 / l)      # own lanes right
            o = _merge(mask, o_j, o)
            lse = jnp.where(head_lane == g * len(masks) + j, m + jnp.log(l), lse)
        o_ref[0, :, cols] = o.astype(o_ref.dtype)
    lse_ref[0] = lse


def _short_attn_bwd_kernel(qkv_ref, o_ref, do_ref, lse_ref, dqkv_ref, *,
                           heads, head_dim, scale):
    hd = heads * head_dim
    masks = _head_masks(head_dim)
    head_lane = lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    lse_all = lse_ref[0]                                      # [S, H] f32
    for g, cols in _groups(heads, head_dim):
        q = qkv_ref[0, :, cols]
        k = qkv_ref[0, :, _shift(cols, hd)]
        v = qkv_ref[0, :, _shift(cols, 2 * hd)]
        do = do_ref[0, :, cols]
        # rowsum(dO * O) = rowsum(P * dP): the softmax backward's reduction
        # without the [S,S] product
        do_o = do.astype(jnp.float32) * o_ref[0, :, cols].astype(jnp.float32)
        dq = dk = dv = None
        for j, mask in enumerate(masks):
            lse = jnp.sum(
                jnp.where(head_lane == g * len(masks) + j, lse_all, 0.0),
                axis=-1, keepdims=True,
            )
            delta = jnp.sum(_only(mask, do_o), axis=-1, keepdims=True)
            s = _dot(_only(mask, q), k, _NT) * scale
            p = jnp.exp(s - lse)                              # [S, S] f32
            dp = _dot(_only(mask, do), v, _NT)
            ds = (p * (dp - delta) * scale).astype(q.dtype)
            dv = _merge(mask, _dot(p.astype(do.dtype), do, _TN), dv)
            dq = _merge(mask, _dot(ds, k), dq)
            dk = _merge(mask, _dot(ds, q, _TN), dk)
        dqkv_ref[0, :, cols] = dq.astype(dqkv_ref.dtype)
        dqkv_ref[0, :, _shift(cols, hd)] = dk.astype(dqkv_ref.dtype)
        dqkv_ref[0, :, _shift(cols, 2 * hd)] = dv.astype(dqkv_ref.dtype)


def _per_image(shape):
    """Block = one image's whole slice of ``[B, ...]``."""
    return pl.BlockSpec((1, *shape[1:]), lambda i: (i,) + (0,) * (len(shape) - 1))


def _call(kernel, name, matmuls, heads, head_dim, interpret, args, out_shapes):
    """One image a grid step. ``matmuls``: the ``[S,S,D]`` products a head
    takes (2 forward, 5 backward with the recomputed scores), for the cost
    XLA's scheduler and its flop count see in place of an opaque call."""
    b, s = args[0].shape[:2]
    out_specs = [_per_image(o.shape) for o in out_shapes]
    nbytes = lambda t: math.prod(t.shape) * jnp.dtype(t.dtype).itemsize
    return pl.pallas_call(
        functools.partial(
            kernel, heads=heads, head_dim=head_dim, scale=1.0 / math.sqrt(head_dim)
        ),
        grid=(b,),
        in_specs=[_per_image(a.shape) for a in args],
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=matmuls * 2 * b * heads * s * s * head_dim,
            transcendentals=b * heads * s * s,
            bytes_accessed=sum(map(nbytes, (*args, *out_shapes))),
        ),
        interpret=interpret,
        name=name,
    )(*args)


# jitted, so that the layers of one model trace and lower each kernel once:
# twelve ViT-B/16 call sites cost seconds of set-up as twelve separate lowerings
@functools.partial(jax.jit, static_argnums=(1, 2))
def _fwd(qkv, heads, interpret):
    b, s, cols = qkv.shape
    hd = cols // 3
    return _call(
        _short_attn_fwd_kernel, "short_attn_fwd", 2, heads, hd // heads, interpret,
        (qkv,),
        [jax.ShapeDtypeStruct((b, s, hd), qkv.dtype),
         jax.ShapeDtypeStruct((b, s, heads), jnp.float32)],
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _short_attention(qkv, heads, interpret):
    return _fwd(qkv, heads, interpret)[0]


def _short_attention_fwd(qkv, heads, interpret):
    o, lse = _fwd(qkv, heads, interpret)
    return o, (qkv, o, lse)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _bwd(qkv, o, lse, do, heads, interpret):
    return _call(
        _short_attn_bwd_kernel, "short_attn_bwd", 5, heads, o.shape[-1] // heads,
        interpret, (qkv, o, do.astype(o.dtype), lse),
        [jax.ShapeDtypeStruct(qkv.shape, qkv.dtype)],
    )[0]


def _short_attention_bwd(heads, interpret, res, do):
    return (_bwd(*res, do, heads, interpret),)


_short_attention.defvjp(_short_attention_fwd, _short_attention_bwd)


def short_attention(qkv, heads: int, *, interpret: bool | None = None):
    """Softmax attention over the packed projection: ``qkv [B, S, 3*H*D]``
    in q|k|v-major column order -> ``[B, S, H*D]`` (heads side by side, as
    the output projection reads them). Same contract as
    :func:`tpu_dist.nn.attention.full_attention`: non-causal, softmax in
    f32, output in ``qkv.dtype``. The shape must pass :func:`fits`.

    ``interpret=None`` selects Pallas interpret mode off the TPU."""
    b, s, cols = qkv.shape
    if cols % (3 * heads):
        raise ValueError(f"{cols} qkv columns do not hold 3 x {heads} heads")
    head_dim = cols // (3 * heads)
    if not fits(s, heads, head_dim, qkv.dtype):
        raise ValueError(
            f"short_attention cannot take S={s}, heads={heads}, head_dim={head_dim} "
            f"({qkv.dtype}): heads must fill whole 128-lane groups and the step's "
            f"working set ({vmem_bytes(s, heads, head_dim, qkv.dtype.itemsize)} B) "
            f"must stay within {VMEM_BUDGET_BYTES} B of VMEM"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _short_attention(qkv, heads, interpret)
