"""Pallas flash attention: tiled online-softmax attention for TPU.

The XLA path (``tpu_dist.nn.attention.full_attention``) materializes the
[S, S] score matrix in HBM — fine at ViT lengths, ruinous for long
context. This kernel computes attention in (block_q × block_k) VMEM tiles
with the numerically-stable online softmax (running max ``m``, normalizer
``l``), so peak memory is O(block²) per core instead of O(S²), and the
QKᵀ / PV matmuls hit the MXU back to back from VMEM.

This is the single-device building block of the long-context story; the
sequence-PARALLEL dimension is handled one level up by
``tpu_dist.nn.attention.ring_attention`` (K/V rotating over the mesh
axis), whose per-rotation local block can itself be this kernel.

No reference counterpart (the reference has no attention code at all,
SURVEY §2.3); the role model is apex/FlashAttention-style fused kernels
on the CUDA side — built here the TPU way: ``pl.pallas_call`` over a
(batch·heads, S/block_q, S/block_k) grid, f32 accumulation in VMEM
scratch, sequential innermost grid dimension carrying the softmax state.

Backward: a ``jax.custom_vjp`` running the FlashAttention-2 dq/dk/dv
recipe as two tiled Pallas kernels (default ``bwd='pallas'``): a dK/dV
pass gridded over k-blocks accumulating across q-blocks in VMEM scratch,
and a dQ pass gridded the other way — probabilities recomputed blockwise
from the saved (m, l) statistics, O(block²) working set, never
materializing [S, S]. The original XLA-level ``lax.scan`` formulation is
kept behind ``bwd='xla'`` for A/B comparison; nothing selects it on its own.

Off-TPU the kernels run in Pallas interpret mode (auto-selected), which is
how the CPU test suite checks them against the XLA path
(``tests/test_flash_attention.py``); on the chip they compile
(``chip_smoke.py`` checks that, and the same comparison, there).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() NaN-free
_LANES = 128      # lane width: the last dim of every VMEM tile

# Per-row statistics (m, l, delta) cross the kernel boundary LANE-BROADCAST,
# as [BH, S, _LANES] arrays in (1, block_q, _LANES) blocks. The Pallas TPU
# lowering refuses a (1, block_q) block over a [BH, S] array (v5e, PR 21: the
# last two block dims must divide by (8, 128) or equal the array's).
# Lane-broadcast, any block_q that is a multiple of 8 is legal and a statistic
# loads as the column it is used as; the price is 128x the statistics' HBM
# traffic. The wrappers slice / broadcast at the call boundary, so residuals
# and the ring merge stay [BH, S].


def _col(stat):
    """[block_q, _LANES] lane-broadcast statistic -> its [block_q, 1] column."""
    return stat[:, :1]


def _lane_broadcast(stat, block_q):
    """[BH, S] statistic -> [BH, S padded to block_q, _LANES] kernel input."""
    stat = _pad_to(stat, block_q, 1)
    return jnp.broadcast_to(stat[..., None], (*stat.shape, _LANES))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                acc_scr, m_scr, l_scr, *, scale, causal, block_q, block_k,
                kv_len, out_dtype):
    i = pl.program_id(1)
    j = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _accumulate(masked):
        # operands reach the MXU in their own dtype (bf16 under the bf16
        # policy), products accumulate in f32; the statistics are f32
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                         # [bq, bk]
        if masked:
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = k_pos < kv_len                         # kv padding
            if causal:
                q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                mask = jnp.logical_and(mask, q_pos >= k_pos)
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = _col(m_scr[:])                           # [bq, 1]
        l_prev = _col(l_scr[:])
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)                   # exact zeros
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # tiles entirely above the diagonal are all-masked: p would be 0, m/l/acc
    # unchanged — their matmuls are skipped (same guard as the bwd); only a
    # tile the diagonal or the padding runs through pays for a mask
    _by_tile(_accumulate, i, j, causal, block_q, block_k, kv_len)

    @pl.when(j == n_k - 1)
    def _finish():
        l_fin = _col(l_scr[:])
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_fin, 1e-30)).astype(out_dtype)
        m_ref[0] = m_scr[:]
        l_ref[0] = l_scr[:]


def _by_tile(accumulate, i, j, causal, block_q, block_k, kv_len, q_len=None):
    """Run ``accumulate(masked)`` for the (q-block ``i``, k-block ``j``) tile:
    not at all where the tile lies above the causal diagonal, with the mask
    where the diagonal or the padding of either side runs through it, and
    without it (no iota, no compare, no select over ``[bq, bk]``) everywhere
    else, which on a long sequence is nearly every tile."""
    padded = (j + 1) * block_k > kv_len
    if q_len is not None:
        padded = jnp.logical_or(padded, (i + 1) * block_q > q_len)
    if causal:
        crossed = (j + 1) * block_k - 1 > i * block_q      # a key past the first query
        masked = jnp.logical_or(padded, crossed)
        live = _causal_block_live(i, j, block_q, block_k)
        pl.when(jnp.logical_and(live, masked))(lambda: accumulate(True))
        pl.when(jnp.logical_and(live, jnp.logical_not(masked)))(lambda: accumulate(False))
    else:
        pl.when(padded)(lambda: accumulate(True))
        pl.when(jnp.logical_not(padded))(lambda: accumulate(False))


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _last_live_k(i, block_q, block_k):
    """Last k-block a causal q-block ``i`` reads. Index maps clamp to it, so
    a tile above the diagonal names the block already in VMEM and costs no
    copy (its matmuls are skipped in the kernel)."""
    return ((i + 1) * block_q - 1) // block_k


def _first_live_q(j, block_q, block_k):
    """First q-block a causal k-block ``j`` is read by (dK/dV pass)."""
    return (j * block_k) // block_q


# jitted, so that every call site of one shape traces and lowers a kernel once
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _fwd(q3, k3, v3, causal, block_q, block_k, interpret, out_dtype=None, group=1):
    """[BH, S, D] queries, [BH/group, S, D] keys and values → (out [BH, S, D],
    m [BH, S], l [BH, S]). ``group`` consecutive query heads read one
    key/value head by index: nothing is copied ``group`` times.

    ``out_dtype`` overrides the output dtype (default: ``q3.dtype``) — the
    ring composition asks for f32 so per-rotation partials merge without a
    bf16 quantization per rotation."""
    bh, s_q, d = q3.shape
    s_kv = k3.shape[1]
    bq = min(block_q, -(-s_q // 8) * 8)   # block ≤ padded length, 8-row tiles
    bk = min(block_k, -(-s_kv // 8) * 8)
    qp = _pad_to(q3, bq, 1)
    kp = _pad_to(k3, bk, 1)
    vp = _pad_to(v3, bk, 1)
    n_q = qp.shape[1] // bq
    n_k = kp.shape[1] // bk
    # d is a static Python shape int: float() runs at trace time, no sync
    scale = 1.0 / float(d) ** 0.5  # tpu-dist: ignore[TD001]

    odt = out_dtype or q3.dtype
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        kv_len=s_kv, out_dtype=odt,
    )
    mem = {"memory_space": pltpu.VMEM}

    def kv_block(b, i, j):
        if causal:
            j = jnp.minimum(j, _last_live_k(i, bq, bk))
        return (b // group, j, 0)

    out, m, l = pl.pallas_call(
        kern,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), **mem),
            pl.BlockSpec((1, bk, d), kv_block, **mem),
            pl.BlockSpec((1, bk, d), kv_block, **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), **mem),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0), **mem),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape, odt),
            jax.ShapeDtypeStruct((*qp.shape[:2], _LANES), jnp.float32),
            jax.ShapeDtypeStruct((*qp.shape[:2], _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        # only the innermost (k-block) dim carries softmax state between
        # iterations; batch·heads and q-blocks are free for the TPU to
        # parallelize/pipeline
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :s_q], m[:, :s_q, 0], l[:, :s_q, 0]


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref,
                    i, j, *, scale, causal, block_q, block_k, q_len, kv_len, masked):
    """Shared backward block math: recompute the probability block ``p``
    and the score-gradient block ``ds`` from the saved (m, l) statistics.
    One definition, used by BOTH backward kernels — the masking and the
    renormalization clamp must never desync between the dq and dk/dv
    passes. Returns ``(q, do, p, ds)`` blocks: ``q``/``do`` as loaded,
    ``p``/``ds`` in f32 (the callers cast them to the operands' dtype for
    their products, which accumulate in f32)."""
    q, do = q_ref[0], do_ref[0]                            # [bq, d]
    k, v = k_ref[0], v_ref[0]                              # [bk, d]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                              # [bq, bk]

    # log-sum-exp of the row: one subtraction a score, no division
    lse = _col(m_ref[0]) + jnp.log(jnp.maximum(_col(l_ref[0]), 1e-30))  # [bq, 1]
    p = jnp.exp(s - lse)                                   # [bq, bk]
    if masked:
        q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # padded q rows carry zero m/l from _pad_to — mask them out explicitly
        mask = jnp.logical_and(q_pos < q_len, k_pos < kv_len)
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                      # [bq, bk]
    ds = p * (dp - _col(delta_ref[0])) * scale
    return q, do, p, ds


def _causal_block_live(i, j, block_q, block_k):
    """False iff the (q-block i, k-block j) tile lies entirely above the
    causal diagonal (max q_pos < min k_pos) — those tiles are all-masked,
    so all three kernels (forward, dK/dV, dQ) skip their matmuls (~2×
    fewer FLOPs at long S; the running state provably doesn't change:
    p would be exactly 0 and m_new == m_prev even at the _NEG_INF init)."""
    return (i + 1) * block_q - 1 >= j * block_k


def _bwd_dkdv_kernel(q_ref, do_ref, m_ref, l_ref, delta_ref, k_ref, v_ref,
                     dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                     block_q, block_k, q_len, kv_len, k_dtype, v_dtype, n_q):
    """dK/dV pass (FlashAttention-2): one (batch·kv-head, k-block) per grid
    point, accumulating in VMEM scratch over the q-blocks of every query
    head that reads this key/value head — the innermost grid dim is that
    (head, q-block) loop, declared ``arbitrary`` so only it is sequential."""
    j = pl.program_id(1)
    t = pl.program_id(2)
    i = t % n_q

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate(masked):
        q, do, p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref, i, j,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            q_len=q_len, kv_len=kv_len, masked=masked,
        )
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # p^T do: [bk, d]
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # ds^T q: [bk, d]

    _by_tile(_accumulate, i, j, causal, block_q, block_k, kv_len, q_len)

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(k_dtype)
        dv_ref[0] = dv_scr[:].astype(v_dtype)


def _bwd_dq_kernel(k_ref, v_ref, q_ref, do_ref, m_ref, l_ref, delta_ref,
                   dq_ref, dq_scr, *, scale, causal, block_q, block_k,
                   q_len, kv_len, out_dtype):
    """dQ pass: one (batch·head, q-block) per grid point, accumulating over
    k-blocks (innermost, sequential) in VMEM scratch."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _accumulate(masked):
        _, _, _, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref, i, j,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            q_len=q_len, kv_len=kv_len, masked=masked,
        )
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _by_tile(_accumulate, i, j, causal, block_q, block_k, kv_len, q_len)

    @pl.when(j == n_k - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(out_dtype)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10),
                   static_argnames=("grad_dtype", "group"))
def _bwd_pallas(q3, k3, v3, o3, m, l, do3, causal, block_q, block_k, interpret,
                delta=None, grad_dtype=None, group=1):
    """Pallas FlashAttention-2 backward: two tiled passes (dK/dV then dQ),
    O(block²) VMEM working set, never materializing [S, S] — the TPU-kernel
    sibling of the XLA-level ``_bwd_blocked`` (``bwd='xla'``, kept for A/B).

    ``delta`` (rowsum(do·o), [BH, S]) may be passed precomputed — the ring
    backward hoists it out of its rotation scan (it is K/V-independent).
    ``grad_dtype`` overrides the output dtypes (default: each input's own
    dtype) — the ring backward asks for f32 so per-rotation grad partials
    accumulate without a bf16 quantization per rotation (same invariant
    as the forward's ``out_dtype`` override).
    """
    bh, s_q, d = q3.shape
    s_kv = k3.shape[1]
    bq = min(block_q, -(-s_q // 8) * 8)
    bk = min(block_k, -(-s_kv // 8) * 8)
    # d is a static Python shape int: float() runs at trace time, no sync
    scale = 1.0 / float(d) ** 0.5  # tpu-dist: ignore[TD001]
    dq_dtype = grad_dtype or q3.dtype
    dk_dtype = grad_dtype or k3.dtype
    dv_dtype = grad_dtype or v3.dtype

    if delta is None:
        delta = jnp.sum(
            do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1
        )                                                  # [BH, S]
    qp = _pad_to(q3, bq, 1)
    dop = _pad_to(do3, bq, 1)
    mp = _lane_broadcast(m, bq)
    lp = _lane_broadcast(l, bq)
    deltap = _lane_broadcast(delta, bq)
    kp = _pad_to(k3, bk, 1)
    vp = _pad_to(v3, bk, 1)
    n_q = qp.shape[1] // bq
    n_k = kp.shape[1] // bk
    mem = {"memory_space": pltpu.VMEM}

    def q_block(b, j, t):
        # t runs over the group's query heads, each over its q-blocks
        i = t % n_q
        if causal:
            i = jnp.maximum(i, _first_live_q(j, bq, bk))
        return (b * group + t // n_q, i, 0)

    def kv_block(b, i, j):
        if causal:
            j = jnp.minimum(j, _last_live_k(i, bq, bk))
        return (b // group, j, 0)

    q_specs = [
        pl.BlockSpec((1, bq, d), q_block, **mem),        # q
        pl.BlockSpec((1, bq, d), q_block, **mem),        # do
        pl.BlockSpec((1, bq, _LANES), q_block, **mem),   # m
        pl.BlockSpec((1, bq, _LANES), q_block, **mem),   # l
        pl.BlockSpec((1, bq, _LANES), q_block, **mem),   # delta
    ]
    kv_specs = [
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), **mem),  # k
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), **mem),  # v
    ]
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel, scale=scale, causal=causal, block_q=bq,
            block_k=bk, q_len=s_q, kv_len=s_kv,
            k_dtype=dk_dtype, v_dtype=dv_dtype, n_q=n_q,
        ),
        grid=(bh // group, n_k, group * n_q),
        in_specs=q_specs + kv_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), **mem),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(kp.shape, dk_dtype),
            jax.ShapeDtypeStruct(vp.shape, dv_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, dop, mp, lp, deltap, kp, vp)

    dq, = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, block_q=bq,
            block_k=bk, q_len=s_q, kv_len=s_kv, out_dtype=dq_dtype,
        ),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bk, d), kv_block, **mem),                   # k
            pl.BlockSpec((1, bk, d), kv_block, **mem),                   # v
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), **mem),  # q
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), **mem),  # do
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0), **mem),  # m
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0), **mem),  # l
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0), **mem),  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), **mem),
        ],
        out_shape=[jax.ShapeDtypeStruct(qp.shape, dq_dtype)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kp, vp, qp, dop, mp, lp, deltap)
    return dq[:, :s_q], dk[:, :s_kv], dv[:, :s_kv]


def _bwd_blocked(q3, k3, v3, o3, m, l, do3, causal, block_k):
    """FlashAttention-2 backward at the XLA level: a scan over K/V blocks
    recomputing P from the saved (m, l) — never materializes [S, S]."""
    bh, s_q, d = q3.shape
    s_kv = k3.shape[1]
    # d is a static Python shape int: float() runs at trace time, no sync
    scale = 1.0 / float(d) ** 0.5  # tpu-dist: ignore[TD001]
    bk = min(block_k, s_kv)

    qf = q3.astype(jnp.float32)
    dof = do3.astype(jnp.float32)
    delta = jnp.sum(dof * o3.astype(jnp.float32), axis=-1)          # [BH,S]

    kp = _pad_to(k3, bk, 1).astype(jnp.float32)
    vp = _pad_to(v3, bk, 1).astype(jnp.float32)
    n_k = kp.shape[1] // bk
    kb = kp.reshape(bh, n_k, bk, d).transpose(1, 0, 2, 3)           # [nk,BH,bk,d]
    vb = vp.reshape(bh, n_k, bk, d).transpose(1, 0, 2, 3)

    q_pos = jnp.arange(s_q)[None, :, None]                          # [1,Sq,1]

    def body(carry, blk):
        dq, j = carry
        kj, vj = blk
        s = jnp.einsum("bqd,bkd->bqk", qf, kj) * scale              # [BH,Sq,bk]
        k_pos = j * bk + jnp.arange(bk)[None, None, :]
        mask = k_pos < s_kv
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
        p = p / jnp.maximum(l, 1e-30)[..., None]
        dv_j = jnp.einsum("bqk,bqd->bkd", p, dof)
        dp = jnp.einsum("bqd,bkd->bqk", dof, vj)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kj)
        dk_j = jnp.einsum("bqk,bqd->bkd", ds, qf)
        return (dq, j + 1), (dk_j, dv_j)

    (dq, _), (dk_b, dv_b) = lax.scan(
        body, (jnp.zeros_like(qf), jnp.int32(0)), (kb, vb)
    )
    dk = dk_b.transpose(1, 0, 2, 3).reshape(bh, n_k * bk, d)[:, :s_kv]
    dv = dv_b.transpose(1, 0, 2, 3).reshape(bh, n_k * bk, d)[:, :s_kv]
    return dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q3, k3, v3, causal, block_q, block_k, interpret, bwd, group):
    out, _, _ = _fwd(q3, k3, v3, causal, block_q, block_k, interpret, None, group)
    return out


def _flash_fwd(q3, k3, v3, causal, block_q, block_k, interpret, bwd, group):
    out, m, l = _fwd(q3, k3, v3, causal, block_q, block_k, interpret, None, group)
    return out, (q3, k3, v3, out, m, l)


def _flash_bwd(causal, block_q, block_k, interpret, bwd, group, res, do3):
    q3, k3, v3, o3, m, l = res
    if bwd == "pallas":
        return _bwd_pallas(
            q3, k3, v3, o3, m, l, do3, causal, block_q, block_k, interpret,
            group=group,
        )
    if group == 1:
        return _bwd_blocked(q3, k3, v3, o3, m, l, do3, causal, block_k)
    # the XLA formulation has no grouped form: give every query head its
    # key/value head's copy and sum the group's gradients
    dq, dk, dv = _bwd_blocked(
        q3, jnp.repeat(k3, group, axis=0), jnp.repeat(v3, group, axis=0),
        o3, m, l, do3, causal, block_k,
    )
    fold = lambda g: g.astype(jnp.float32).reshape(  # noqa: E731
        (-1, group) + g.shape[1:]).sum(axis=1)
    return dq, fold(dk).astype(k3.dtype), fold(dv).astype(v3.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Ring flash attention: the Pallas kernels composed with sequence-parallel
# K/V rotation (the ring-attention scheme of nn/attention.py), so BOTH
# memory dimensions are tiled — across devices by the ring, within a device
# by the kernel. The trick that makes the composition cheap: under the ring,
# causal masking at a given rotation is block-structured — the (my, kv_idx)
# pair is either fully unmasked (kv_idx < my), fully masked (kv_idx > my),
# or the diagonal (kv_idx == my), where global offsets cancel and the
# kernel's RELATIVE causal mask is exactly right. A 3-way lax.switch per
# rotation picks the variant; no global-position plumbing enters the
# kernels. Backward follows the ring-flash recipe: dq accumulates at home,
# (dk, dv) accumulators rotate WITH k/v and arrive home after the full
# cycle; each rotation reuses the FlashAttention-2 kernels with the global
# (m, l, delta) statistics, which are valid for any K/V block.
# ---------------------------------------------------------------------------


def _ring_perm(n):
    return [(i, (i + 1) % n) for i in range(n)]


def _fwd_variants(q3, k3, v3, block_q, block_k, interpret):
    """(full, diagonal-causal, masked) rotation forwards, lax.switch-ready.
    Each returns (out_j [BH,S,D] f32, m_j [BH,S], l_j [BH,S]) — partials
    stay f32 so the cross-rotation merge never quantizes to the input
    dtype (one bf16 round-off per rotation would otherwise accumulate)."""
    def full(kk, vv):
        return _fwd(
            q3, kk, vv, False, block_q, block_k, interpret,
            out_dtype=jnp.float32,
        )

    def diag(kk, vv):
        return _fwd(
            q3, kk, vv, True, block_q, block_k, interpret,
            out_dtype=jnp.float32,
        )

    def masked(kk, vv):
        bh, s_q, _ = q3.shape
        return (
            jnp.zeros(q3.shape, jnp.float32),
            jnp.full((bh, s_q), _NEG_INF, jnp.float32),
            jnp.zeros((bh, s_q), jnp.float32),
        )

    return full, diag, masked


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q3, k3, v3, axis_name, causal, block_q, block_k, interpret):
    out, _, _ = _ring_flash_fwd_impl(
        q3, k3, v3, axis_name, causal, block_q, block_k, interpret
    )
    return out


def _ring_flash_fwd_impl(q3, k3, v3, axis_name, causal, block_q, block_k,
                         interpret):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    bh, s_q, d = q3.shape
    full, diag, masked = _fwd_variants(q3, k3, v3, block_q, block_k, interpret)

    def rotation(carry, _):
        m, l, acc, kk, vv, kv_idx = carry
        if causal:
            case = jnp.where(kv_idx < my, 0, jnp.where(kv_idx == my, 1, 2))
            out_j, m_j, l_j = lax.switch(case, (full, diag, masked), kk, vv)
        else:
            out_j, m_j, l_j = full(kk, vv)
        # merge the rotation's (normalized) block into the running stats
        m_new = jnp.maximum(m, m_j)
        corr = jnp.exp(m - m_new)          # m starts at _NEG_INF (finite)
        corr_j = jnp.exp(m_j - m_new)
        acc = acc * corr[..., None] + out_j * (l_j * corr_j)[..., None]
        l = l * corr + l_j * corr_j
        perm = _ring_perm(n)
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return (m_new, l, acc, kk, vv, (kv_idx - 1) % n), None

    m0 = jnp.full((bh, s_q), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, s_q), jnp.float32)
    acc0 = jnp.zeros((bh, s_q, d), jnp.float32)
    (m, l, acc, _, _, _), _ = lax.scan(
        rotation, (m0, l0, acc0, k3, v3, my), None, length=n
    )
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q3.dtype)
    return out, m, l


def _ring_flash_fwd(q3, k3, v3, axis_name, causal, block_q, block_k, interpret):
    out, m, l = _ring_flash_fwd_impl(
        q3, k3, v3, axis_name, causal, block_q, block_k, interpret
    )
    return out, (q3, k3, v3, out, m, l)


def _ring_flash_bwd(axis_name, causal, block_q, block_k, interpret, res, do3):
    q3, k3, v3, o3, m, l = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    # delta is K/V-independent: compute ONCE, not per rotation
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)

    def blk(kk, vv, blk_causal):
        dq_j, dk_j, dv_j = _bwd_pallas(
            q3, kk, vv, o3, m, l, do3, blk_causal, block_q, block_k,
            interpret, delta=delta, grad_dtype=jnp.float32,
        )
        return dk_j, dv_j, dq_j

    def full(kk, vv):
        return blk(kk, vv, False)

    def diag(kk, vv):
        return blk(kk, vv, True)

    def masked(kk, vv):
        # must match full/diag's grad_dtype=f32 exactly — lax.switch
        # requires identical branch output types, and k/v/q may be bf16
        return (jnp.zeros_like(kk, jnp.float32),
                jnp.zeros_like(vv, jnp.float32),
                jnp.zeros_like(q3, jnp.float32))

    def rotation(carry, _):
        kk, vv, dka, dva, dq, kv_idx = carry
        if causal:
            case = jnp.where(kv_idx < my, 0, jnp.where(kv_idx == my, 1, 2))
            dk_j, dv_j, dq_j = lax.switch(case, (full, diag, masked), kk, vv)
        else:
            dk_j, dv_j, dq_j = full(kk, vv)
        dka = dka + dk_j
        dva = dva + dv_j
        dq = dq + dq_j.astype(dq.dtype)
        # the grad accumulators ride the ring WITH their k/v block; after
        # the full cycle they arrive back at the block's home device
        perm = _ring_perm(n)
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        dka = lax.ppermute(dka, axis_name, perm)
        dva = lax.ppermute(dva, axis_name, perm)
        return (kk, vv, dka, dva, dq, (kv_idx - 1) % n), None

    dq0 = jnp.zeros(q3.shape, jnp.float32)
    (kk, vv, dka, dva, dq, _), _ = lax.scan(
        rotation,
        (k3, v3, jnp.zeros_like(k3, jnp.float32),
         jnp.zeros_like(v3, jnp.float32), dq0, my),
        None,
        length=n,
    )
    return dq.astype(q3.dtype), dka.astype(k3.dtype), dva.astype(v3.dtype)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(q, k, v, axis_name: str, *, causal: bool = False,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool | None = None):
    """Sequence-parallel flash attention on [B, S_local, H, D] shards —
    drop-in for :func:`tpu_dist.nn.attention.ring_attention` with the
    local tile computed by the Pallas kernels instead of an XLA einsum.
    Per-device peak memory drops from O(S_local²) (the ring's per-rotation
    score tile) to O(block²); causal rotations entirely above the diagonal
    are skipped (a 3-way ``lax.switch``). Call inside ``shard_map`` with
    the sequence dim sharded over ``axis_name``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, d = q.shape
    to3 = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, t.shape[1], d)
    out3 = _ring_flash(
        to3(q), to3(k), to3(v), axis_name, causal, block_q, block_k, interpret
    )
    return out3.reshape(b, h, s, d).transpose(0, 2, 1, 3)


LONG_SEQ = 2048    # from here on the tiles are LONG_BLOCK wide where that divides
# 128-wide tiles spend a long sequence on grid steps and on rescaling the
# accumulator once a k-block: forward + backward at S=8192, 32 heads on 2,
# takes 66 ms at 256x512, 54 at 512x512, 43 at 512x1024, 40 at 1024x1024
# (v5e, my chip run, PR 33); 64-wide heads choose the same (the docstring of
# flash_attention has their readings, PR 35)
LONG_BLOCK = 1024


def flash_attention(q, k, v, *, causal: bool = False, block_q: int | None = None,
                    block_k: int | None = None, interpret: bool | None = None,
                    bwd: str = "pallas"):
    """Tiled attention on [B, S, H, D] — drop-in for
    :func:`tpu_dist.nn.attention.full_attention` (same contract: f32
    softmax accumulation, output in ``q.dtype``). The matmuls take their
    operands in the inputs' dtype (bf16 under the bf16 policy) and
    accumulate in f32; the softmax statistics are f32.

    Grouped heads: ``k``/``v`` may have ``H / g`` heads; query head ``h``
    reads key/value head ``h // g`` by index, and dK/dV accumulate over the
    group inside the kernel. Tiles are 128 wide, and ``LONG_BLOCK`` wide from
    ``LONG_SEQ`` tokens on where that divides the sequence, unless given.

    ``interpret=None`` auto-selects Pallas interpret mode off-TPU. Heads of
    64 channels run as whole 64-wide blocks at half the rate of 128-wide
    ones: on the v5e, causal, 4 x 8,192 tokens, 32 query heads on 8, forward
    22.2 ms and forward + backward 81.3 ms at 1,024 x 1,024 tiles (20% of
    attention's roofline; 128-wide heads, 32 on 2, the same operations:
    43%), because a 64-deep contraction (``q k^T``, ``dO v^T``) and a 64-wide
    result (``p v``, ``ds^T q``, ``ds k``) each fill half of the 128 x 128
    matrix unit. Smaller tiles are slower there too (512 x 1,024: 26.5 /
    88.8 ms; 1,024 x 512: 41.4 / 103.9; 512 x 512: 43.2 / 110.8) and 2,048-wide
    ones exceed VMEM (my chip run, PR 35). Sequence lengths are padded to
    the block size internally and masked exactly.

    ``bwd``: ``'pallas'`` (default) runs the FlashAttention-2 backward as
    two tiled Pallas kernels (dK/dV pass + dQ pass); ``'xla'`` keeps the
    blockwise ``lax.scan`` formulation — same math, for A/B comparison
    and as a numerics cross-check. (Either way the FORWARD needs the
    Pallas module; off-TPU both run in interpret mode.)
    """
    if bwd not in ("pallas", "xla"):
        raise ValueError(f"bwd must be 'pallas' or 'xla', got {bwd!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, d = q.shape
    if h % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{h} query heads do not group over {k.shape[2]}/{v.shape[2]} key/value heads")
    block = LONG_BLOCK if s >= LONG_SEQ and s % LONG_BLOCK == 0 else 128
    to3 = lambda t: t.transpose(0, 2, 1, 3).reshape(-1, t.shape[1], d)
    out3 = _flash(to3(q), to3(k), to3(v), causal, block_q or block, block_k or block,
                  interpret, bwd, h // k.shape[2])
    return out3.reshape(b, h, s, d).transpose(0, 2, 1, 3)
