"""Attention: full (single-device) and ring (sequence-parallel) variants.

The reference has no attention code at all (SURVEY §2.3: models are conv
ResNets); its BASELINE north star adds ViT-B/16 as a data-parallel stress
test. This module goes further and makes long-context support first-class,
TPU-style:

* :func:`full_attention` — plain softmax attention; one fused XLA op chain,
  MXU-friendly einsums, f32 softmax accumulation under bf16 compute.
* :func:`ring_attention` — sequence parallelism over a mesh axis: Q stays
  local while K/V blocks rotate around the ring via ``lax.ppermute``
  (ICI-neighbor traffic only), with flash-style online-softmax accumulation
  so the full [S, S] score matrix never materializes. Per-device memory is
  O(S_local · S_block) and the sequence dimension scales with the number of
  devices on the axis. Combine with the ``data`` axis on a 2-D mesh for
  DP × SP.
* :func:`ulysses_attention` — the all-to-all alternative: tokens↔heads
  redistribution so each device runs full-sequence attention for H/n
  heads (two collectives per call; composes with the Pallas flash
  kernel). Pick by topology: ring = nearest-neighbor ICI traffic,
  ulysses = fewer collectives and flash-compatible, needs heads % n == 0.
* :func:`projected_attention` — the entry a transformer block calls: the
  fused qkv projection and the attention over it. It chooses by shape: on a
  TPU, with no sequence axis, where one image's whole ``[S, S]`` score tile
  and its operands fit VMEM (ViT lengths), the projection is laid out
  q|k|v-major and ``ops/short_attention.py`` runs forward and backward as
  one Pallas kernel each, with no ``[., S, S]`` array and no relayout copy
  in HBM. Everything else slices q, k, v out of the head-major projection
  and goes through :func:`attention`. Each call site lowered is counted:
  ``attn.sites_fused`` / ``attn.sites_xla`` in ``obs/counters``.

The first three operate on [B, S, H, D] (batch, sequence, heads, head_dim)
and are shape-polymorphic under ``shard_map``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from tpu_dist.obs import counters

# Process-global default for the single-device attention implementation.
# "auto": chosen by shape — the whole-sequence Pallas kernel
# (ops/short_attention.py) where projected_attention finds that it fits,
# the XLA chain everywhere else. "xla": always the einsum/softmax chain
# ([S,S] scores in HBM). "flash": always the Pallas tiled kernel
# (ops/flash_attention.py), O(block²) memory, the long-context choice.
# The Trainer sets "flash" under ``--flash_attention`` and "auto" otherwise;
# it is process-global state like the XLA compile cache, not per-model.
_IMPLS = ("auto", "xla", "flash")
_DEFAULT_IMPL = "auto"


def set_default_attention_impl(impl: str) -> None:
    global _DEFAULT_IMPL
    _DEFAULT_IMPL = _resolve_impl(impl)


def get_default_attention_impl() -> str:
    return _DEFAULT_IMPL


def _resolve_impl(impl: Optional[str]) -> str:
    impl = impl or _DEFAULT_IMPL
    if impl not in _IMPLS:
        raise ValueError(f"attention impl must be one of {_IMPLS}, got {impl!r}")
    return impl


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


FLASH_MIN_SEQ = 1024  # below it the XLA chain's [S, S] scores are small


def takes_flash_kernel(impl: Optional[str], causal: bool, seq: int, h_dim: int) -> bool:
    """Where :func:`full_attention` takes the tiled kernel
    (``ops/flash_attention.py``) without being told to: a causal site on a
    TPU from ``FLASH_MIN_SEQ`` tokens on, whose ``[S, S]`` scores the XLA
    chain would put in HBM for every head, with heads of 64 channels or whole
    128-lane groups and whole tiles. ``impl`` "flash" takes it at any shape."""
    impl = _resolve_impl(impl)
    if impl != "auto":
        return impl == "flash"
    return causal and _on_tpu() and seq >= FLASH_MIN_SEQ and seq % 128 == 0 and h_dim % 64 == 0


def full_attention(q, k, v, *, causal: bool = False, impl: Optional[str] = None):
    """``q [B,S,H,D]``, ``k``/``v [B,S,Hkv,D]`` → ``[B,S,H,D]``; ``H / Hkv``
    consecutive query heads share a key/value head (``Hkv == H``: plain
    heads). Softmax in f32 regardless of input dtype. Under ``impl`` "auto"
    a long causal site on a TPU takes the tiled kernel
    (:func:`takes_flash_kernel`, counted in ``attn.sites_flash``); anything
    else is the XLA chain here: q, k and v arrive apart, and the
    whole-sequence kernel reads them packed (:func:`projected_attention`)."""
    if takes_flash_kernel(impl, causal, q.shape[1], q.shape[-1]):
        from tpu_dist.ops.flash_attention import flash_attention  # noqa: PLC0415

        counters.inc("attn.sites_flash")
        return flash_attention(q, k, v, causal=causal)
    b, s_q, h, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, s_q, kv, h // kv, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d))
    if causal:
        s_k = scores.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(b, s_q, h, d)


def ring_attention(q, k, v, axis_name: str, *, causal: bool = False):
    """Sequence-parallel attention over ``axis_name`` (ring / all-to-all CP).

    Inside ``shard_map`` with the sequence dim sharded over ``axis_name``:
    every device holds local Q/K/V blocks of shape [B, S/n, H, D]. K/V
    rotate n times around the ring (``lax.ppermute`` to the next neighbor —
    nearest-neighbor ICI traffic, overlapped by XLA with the block matmuls);
    attention is accumulated with the numerically-stable online softmax
    (running max ``m``, normalizer ``l``, accumulator ``acc``).

    ``causal`` masks by GLOBAL position: block order on the axis is the
    sequence order (device i holds positions [i·S/n, (i+1)·S/n)).
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    qf = q.astype(jnp.float32)

    def block(scores_kv, kv_idx):
        """Scores of local Q against the K/V block originating at kv_idx."""
        kk, vv = scores_kv
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kk.astype(jnp.float32)) * scale
        if causal:
            q_pos = my * s_loc + jnp.arange(s_loc)[:, None]        # [Sq,1]
            k_pos = kv_idx * s_loc + jnp.arange(s_loc)[None, :]    # [1,Sk]
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        return s, vv

    def body(carry, _):
        m, l, acc, kk, vv, kv_idx = carry
        s, vv_f = block((kk, vv), kv_idx)
        m_new = jnp.maximum(m, s.max(axis=-1))                     # [B,H,Sq]
        # guard: fully-masked rows keep m at -inf; exp(-inf - -inf) → use 0
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vv_f.astype(jnp.float32)
        )
        # rotate K/V to the next ring position
        perm = [(i, (i + 1) % n) for i in range(n)]
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        kv_idx = (kv_idx - 1) % n
        return (m_new, l_new, acc, kk, vv, kv_idx), None

    m0 = jnp.full((b, h, s_loc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    acc0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    (m, l, acc, _, _, _), _ = lax.scan(
        body, (m0, l0, acc0, k, v, my), None, length=n
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]                   # [B,H,Sq,D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)               # [B,Sq,H,D]


def ulysses_attention(q, k, v, axis_name: str, *, causal: bool = False,
                      impl: Optional[str] = None):
    """All-to-all sequence parallelism (the DeepSpeed-Ulysses scheme —
    the OTHER first-class long-context strategy next to the ring).

    Inside ``shard_map`` with the sequence dim sharded over ``axis_name``:
    one STACKED ``all_to_all`` (q/k/v together) redistributes tokens↔heads
    so each device holds the FULL sequence for ``H/n`` of the heads,
    ordinary single-device attention runs locally (attention never mixes
    heads), and a second ``all_to_all`` restores the token sharding. Two
    collectives per call versus the ring's ``n`` ppermutes; requires
    ``heads % n == 0``.

    Differentiable by plain autodiff (``all_to_all`` transposes to
    ``all_to_all``) — no custom VJP needed. And because the local call IS
    full-sequence attention, the Pallas flash kernel composes directly:
    ``impl="flash"`` (or the process default) runs the tiled kernel on the
    gathered sequence — flash × SP with no extra machinery.
    """
    n = lax.axis_size(axis_name)
    h = q.shape[2]
    if h % n:
        raise ValueError(
            f"ulysses sequence parallelism needs heads ({h}) divisible by "
            f"the axis size ({n}); use sp_mode='ring' otherwise"
        )

    # ONE stacked all_to_all for q/k/v (axes shifted by the leading stack
    # dim), one for the output — two collectives total, as advertised
    qkv = jnp.stack((q, k, v))  # [3, B, S/n, H, D]
    qg, kg, vg = lax.all_to_all(
        qkv, axis_name, split_axis=3, concat_axis=2, tiled=True
    )                           # each [B, S, H/n, D]
    o = full_attention(qg, kg, vg, causal=causal, impl=impl)
    return lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2, tiled=True)


def attention(q, k, v, *, causal: bool = False, seq_axis: Optional[str] = None,
              impl: Optional[str] = None, sp_mode: str = "ring"):
    """Dispatch: sequence-parallel attention when a sequence axis is given
    (``sp_mode``: "ring" rotation or "ulysses" all-to-all), else full
    (``impl``/module default selecting XLA vs Pallas flash).

    What a site takes with no sequence axis, by what it can observe: a
    causal site of ``FLASH_MIN_SEQ`` tokens or more on a TPU takes the tiled
    kernel, grouped heads (``k``/``v`` with fewer heads than ``q``) read by
    index; any other site takes the XLA chain, which handles grouped heads
    too. The sequence-parallel variants take equal head counts only.

    Under the RING the flash impl selects
    :func:`tpu_dist.ops.flash_attention.ring_flash_attention`: the ring
    already tiles ACROSS devices (each rotation sees one [S/n, S/n] local
    tile, never a global [S, S]), and the Pallas kernels tile WITHIN the
    device, taking the per-rotation working set from O(S_local²) HBM down
    to O(block²) VMEM. Under ULYSSES the flash impl applies directly (the
    local computation is full-sequence attention)."""
    if seq_axis is not None:
        if sp_mode == "ulysses":
            return ulysses_attention(q, k, v, seq_axis, causal=causal, impl=impl)
        if sp_mode != "ring":
            raise ValueError(f"sp_mode must be 'ring' or 'ulysses', got {sp_mode!r}")
        if _resolve_impl(impl) == "flash":
            from tpu_dist.ops.flash_attention import (  # noqa: PLC0415
                ring_flash_attention,
            )

            return ring_flash_attention(q, k, v, seq_axis, causal=causal)
        return ring_attention(q, k, v, seq_axis, causal=causal)
    return full_attention(q, k, v, causal=causal, impl=impl)


def qkv_major(t, h_dim: int):
    """Reorder the last axis of a fused projection's weight or bias from
    head-major ``[heads, 3, h_dim]`` (the parameters' layout: a contiguous
    column shard is whole heads) to q|k|v-major ``[3, heads, h_dim]``. Done
    at trace time on the compute-dtype copy the step already makes; its
    transpose carries the gradient back, so parameters, checkpoints and
    ``tp_param_specs`` never see it."""
    heads = t.shape[-1] // (3 * h_dim)
    t = t.reshape(*t.shape[:-1], heads, 3, h_dim)
    return jnp.swapaxes(t, -3, -2).reshape(*t.shape[:-3], 3 * heads * h_dim)


def takes_short_kernel(impl: Optional[str], seq_axis: Optional[str], causal: bool,
                       seq: int, heads: int, h_dim: int, dtype) -> bool:
    """The selection rule of :func:`projected_attention`, from what the call
    can observe: the implementation left to choice, a TPU, no sequence axis,
    no mask, and a shape ``ops/short_attention.py`` can hold in VMEM. A
    causal site never takes it (the kernel has no mask): it goes on to
    :func:`attention`, where :func:`takes_flash_kernel` decides between the
    tiled kernel and XLA; so does a grouped-head site, whose q, k and v are
    no one fused projection."""
    if _resolve_impl(impl) != "auto" or seq_axis is not None or causal or not _on_tpu():
        return False
    from tpu_dist.ops.short_attention import fits  # noqa: PLC0415

    return fits(seq, heads, h_dim, dtype)


def projected_attention(y, proj, h_dim: int, *, causal: bool = False,
                        seq_axis: Optional[str] = None, impl: Optional[str] = None,
                        sp_mode: str = "ring"):
    """Fused qkv projection plus attention: ``y [B, S, Din]`` through
    ``proj = {"w": [Din, H*3*h_dim], "b"}`` (columns head-major; ``H`` is
    the local head count under tensor parallelism) to ``[B, S, H*h_dim]``,
    heads side by side, as the output projection reads it."""
    impl = _resolve_impl(impl)
    w, bias = proj["w"].astype(y.dtype), proj["b"].astype(y.dtype)
    b, s = y.shape[:2]
    heads = w.shape[-1] // (3 * h_dim)
    if takes_short_kernel(impl, seq_axis, causal, s, heads, h_dim, y.dtype):
        from tpu_dist.ops.short_attention import short_attention  # noqa: PLC0415

        counters.inc("attn.sites_fused")
        qkv = y @ qkv_major(w, h_dim) + qkv_major(bias, h_dim)
        return short_attention(qkv, heads, interpret=False)  # only taken on a TPU
    if seq_axis is not None or not takes_flash_kernel(impl, causal, s, h_dim):
        if impl != "flash":
            counters.inc("attn.sites_xla")
    qkv = (y @ w + bias).reshape(b, s, heads, 3, h_dim)
    q, k, v = (qkv[:, :, :, i, :] for i in range(3))
    o = attention(q, k, v, causal=causal, seq_axis=seq_axis, impl=impl, sp_mode=sp_mode)
    return o.reshape(b, s, heads * h_dim)
