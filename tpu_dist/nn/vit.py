"""Vision Transformer (ViT) family — the BASELINE north-star transformer
config ("ViT-B/16 / ImageNet-1k ... stress allreduce on transformer grads",
BASELINE.json configs[4]; the reference itself has no transformer, SURVEY
§2.3).

Same functional contract as :class:`~tpu_dist.nn.resnet.ResNetDef`:
``init(key) -> (params, state)`` / ``apply(params, state, x, train=,
axis_name=, seq_axis=)``. ``state`` is empty (no BatchNorm — LayerNorm
needs no cross-replica sync), so ViT slots into the same Trainer/steps.

``seq_axis`` switches the attention to the sequence-parallel ring variant
(:func:`tpu_dist.nn.attention.ring_attention`) for long-context training
over a 2-D DP×SP mesh; patch tokens must then arrive sharded over that axis.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from tpu_dist.nn import attention as attn_lib
from tpu_dist.obs import hlo_scopes


def _ln_init(dim):
    return {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))}


def _ln_apply(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def _dense_init(key, din, dout):
    kw, kb = jax.random.split(key)
    # transformer practice: truncated-normal-ish small init for stability
    w = jax.random.normal(kw, (din, dout)) * (din ** -0.5)
    return {"w": w, "b": jnp.zeros((dout,))}


def _dense(p, x):
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


def _dense_local(p, x):
    """Matmul only — bias is added by the caller (after any TP psum)."""
    return x @ p["w"].astype(x.dtype)


def patchify(x, patch_size: int):
    """[B, H, W, 3] → [B, N, patch_dim] in row-major patch order (shared by
    ViTDef and ViTMoEDef)."""
    b, h, w, c = x.shape
    ph = pw = patch_size
    x = x.reshape(b, h // ph, ph, w // pw, pw, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // ph) * (w // pw), ph * pw * c)


def block_forward(blk, t, heads: int, attn_impl: Optional[str] = None):
    """One standard (full-attention) transformer block on [B, S, D].
    Shared by ViTDef's sequential path and the pipeline-parallel wrapper.
    ``attn_impl`` pins the attention implementation at build time (None =
    process default at trace time)."""
    with hlo_scopes.scope("vit/norm"):
        y = _ln_apply(blk["ln1"], t)
    with hlo_scopes.scope("vit/qkv"):
        o = attn_lib.projected_attention(y, blk["qkv"], t.shape[-1] // heads, impl=attn_impl)
    with hlo_scopes.scope("vit/attn_out"):
        t = t + _dense(blk["proj"], o)
    with hlo_scopes.scope("vit/norm"):
        y = _ln_apply(blk["ln2"], t)
    with hlo_scopes.scope("vit/mlp"):
        y = jax.nn.gelu(_dense(blk["mlp1"], y))
        return t + _dense(blk["mlp2"], y)


def tp_block_forward(
    blk,
    t,
    h_dim: int,
    copy_to_tp,
    reduce_from_tp,
    *,
    seq_axis: Optional[str] = None,
    sp_mode: str = "ring",
    attn_impl: Optional[str] = None,
):
    """One Megatron-TP transformer block on [B, S, D]: qkv/mlp1 arrive
    column-sharded (local heads / local hidden), proj/mlp2 row-sharded;
    ``copy_to_tp``/``reduce_from_tp`` are the conjugate identity/psum pair
    from :func:`tpu_dist.parallel.tensor.tp_ops`.  Shared by ViTDef's
    sequential TP path and the pipeline-parallel stage scan (PP×TP —
    Megatron's layout: TP inside each pipeline stage)."""
    with hlo_scopes.scope("vit/norm"):
        y = copy_to_tp(_ln_apply(blk["ln1"], t))
    # qkv is col-sharded under TP, layout [heads, 3, h_dim]: a contiguous
    # column shard is whole (local) heads
    with hlo_scopes.scope("vit/qkv"):
        o = attn_lib.projected_attention(
            y, blk["qkv"], h_dim, seq_axis=seq_axis, sp_mode=sp_mode, impl=attn_impl
        )
    with hlo_scopes.scope("vit/attn_out"):
        proj = reduce_from_tp(_dense_local(blk["proj"], o))
        t = t + proj + blk["proj"]["b"].astype(t.dtype)
    with hlo_scopes.scope("vit/norm"):
        y = copy_to_tp(_ln_apply(blk["ln2"], t))
    with hlo_scopes.scope("vit/mlp"):
        y = jax.nn.gelu(_dense(blk["mlp1"], y))  # col-sharded hidden
        return t + reduce_from_tp(_dense_local(blk["mlp2"], y)) + blk["mlp2"]["b"].astype(t.dtype)


def check_pos_capacity(n_tokens: int, pos_table, image_size: int, patch_size: int):
    """Loud error when the input has more patch tokens than the positional
    table (smaller inputs are fine — they use the leading positions)."""
    if n_tokens > pos_table.shape[0]:
        raise ValueError(
            f"input has {n_tokens} patch tokens but the positional embedding "
            f"holds {pos_table.shape[0]} (image_size={image_size}, "
            f"patch_size={patch_size}); build the model with the matching "
            f"image_size"
        )


@dataclass(frozen=True)
class ViTDef:
    image_size: int = 224
    patch_size: int = 16
    dim: int = 768
    depth: int = 12
    heads: int = 12
    mlp_ratio: int = 4
    num_classes: int = 1000
    pool: str = "mean"  # mean-pool tokens (cls-free keeps seq sharding even)

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    def init(self, key, dtype=jnp.float32):
        keys = iter(jax.random.split(key, 16 + 8 * self.depth))
        p: dict = {}
        patch_dim = self.patch_size * self.patch_size * 3
        p["patch"] = _dense_init(next(keys), patch_dim, self.dim)
        p["pos"] = jax.random.normal(next(keys), (self.n_patches, self.dim)) * 0.02
        blocks = []
        for _ in range(self.depth):
            blocks.append(
                {
                    "ln1": _ln_init(self.dim),
                    "qkv": _dense_init(next(keys), self.dim, 3 * self.dim),
                    "proj": _dense_init(next(keys), self.dim, self.dim),
                    "ln2": _ln_init(self.dim),
                    "mlp1": _dense_init(next(keys), self.dim, self.mlp_ratio * self.dim),
                    "mlp2": _dense_init(next(keys), self.mlp_ratio * self.dim, self.dim),
                }
            )
        p["blocks"] = blocks
        p["ln_f"] = _ln_init(self.dim)
        p["head"] = _dense_init(next(keys), self.dim, self.num_classes)
        if dtype != jnp.float32:
            p = jax.tree_util.tree_map(lambda t: t.astype(dtype), p)
        return p, {}

    # -- apply ---------------------------------------------------------------

    def tp_param_specs(self, axis: str):
        """PartitionSpec pytree for Megatron TP over ``axis``: qkv/mlp1
        column-sharded, proj/mlp2 row-sharded, everything else replicated.
        Use for ``shard_map`` in/out specs AND for placing the params
        (``NamedSharding(mesh, spec)`` per leaf)."""
        from jax.sharding import PartitionSpec as P  # noqa: PLC0415

        rep = {"w": P(), "b": P()}
        block = {
            "ln1": {"scale": P(), "bias": P()},
            "qkv": {"w": P(None, axis), "b": P(axis)},
            "proj": {"w": P(axis, None), "b": P()},
            "ln2": {"scale": P(), "bias": P()},
            "mlp1": {"w": P(None, axis), "b": P(axis)},
            "mlp2": {"w": P(axis, None), "b": P()},
        }
        return {
            "patch": dict(rep),
            "pos": P(),
            "blocks": [dict(block) for _ in range(self.depth)],
            "ln_f": {"scale": P(), "bias": P()},
            "head": dict(rep),
        }

    def patchify(self, x):
        """[B, H, W, 3] → [B, N, patch_dim] in row-major patch order."""
        return patchify(x, self.patch_size)

    def apply(
        self,
        params,
        state,
        x,
        *,
        train: bool = False,
        axis_name: Optional[str] = None,  # unused (no BN); kept for contract
        seq_axis: Optional[str] = None,
        sp_mode: str = "ring",
        tp_axis: Optional[str] = None,
        tokens: Optional[jnp.ndarray] = None,
        pos_offset: int = 0,
        attn_impl: Optional[str] = None,
    ):
        """Forward. Either ``x`` as images [B,H,W,3] (patchified here) or
        pre-sharded ``tokens`` [B, S_local, patch_dim] for sequence-parallel
        runs (with ``pos_offset`` the global index of the first local token).

        ``tp_axis``: Megatron tensor parallelism — qkv/mlp1 arrive
        column-sharded (local heads / local hidden), proj/mlp2 row-sharded
        with one ``psum`` each; params must be placed with
        :meth:`tp_param_specs`. Composable with neither ``seq_axis`` nor
        SyncBN (there is no BN).
        """
        del axis_name
        with hlo_scopes.scope("vit/patch_embed"):
            if tokens is None:
                tokens = self.patchify(x)
                if seq_axis is not None:
                    # x arrived replicated over the seq axis: each device keeps
                    # only its contiguous token chunk (ring attention owns the
                    # cross-chunk interaction)
                    n_sp = jax.lax.axis_size(seq_axis)
                    if tokens.shape[1] % n_sp:
                        raise ValueError(
                            f"sequence of {tokens.shape[1]} patch tokens does not "
                            f"divide over {n_sp} sequence-parallel devices — "
                            f"tokens would be silently dropped"
                        )
                    s_loc = tokens.shape[1] // n_sp
                    tokens = jax.lax.dynamic_slice_in_dim(
                        tokens, jax.lax.axis_index(seq_axis) * s_loc, s_loc, axis=1
                    )
            t = _dense(params["patch"], tokens)
            pos = params["pos"].astype(t.dtype)
            if seq_axis is not None:
                idx = jax.lax.axis_index(seq_axis)
                s_loc = t.shape[1]
                pos = jax.lax.dynamic_slice_in_dim(pos, idx * s_loc + pos_offset, s_loc)
            else:
                check_pos_capacity(t.shape[1], pos, self.image_size, self.patch_size)
                pos = pos[: t.shape[1]]  # smaller inputs use the leading positions
            t = t + pos[None]

        if tp_axis is not None:
            from tpu_dist.parallel.tensor import tp_ops  # noqa: PLC0415

            copy_to_tp, reduce_from_tp = tp_ops(tp_axis)
        else:
            copy_to_tp = reduce_from_tp = lambda v: v

        h_dim = self.dim // self.heads
        for blk in params["blocks"]:
            t = tp_block_forward(
                blk, t, h_dim, copy_to_tp, reduce_from_tp,
                seq_axis=seq_axis, sp_mode=sp_mode, attn_impl=attn_impl,
            )

        with hlo_scopes.scope("vit/norm"):
            t = _ln_apply(params["ln_f"], t)
        with hlo_scopes.scope("vit/head"):
            pooled = t.mean(axis=1)
            if seq_axis is not None:
                # token mean over the full (sharded) sequence
                pooled = jax.lax.pmean(pooled, seq_axis)
            return _dense(params["head"], pooled), state


def vit_b16(num_classes: int = 1000, image_size: int = 224) -> ViTDef:
    """ViT-B/16 (86M params at 1000 classes) — BASELINE configs[4]."""
    return ViTDef(image_size=image_size, patch_size=16, dim=768, depth=12,
                  heads=12, num_classes=num_classes)


def vit_s16(num_classes: int = 1000, image_size: int = 224) -> ViTDef:
    return ViTDef(image_size=image_size, patch_size=16, dim=384, depth=12,
                  heads=6, num_classes=num_classes)


def vit_tiny(num_classes: int = 10, image_size: int = 32) -> ViTDef:
    """CIFAR-sized: patch 4 over 32x32 → 64 tokens; for tests/smokes."""
    return ViTDef(image_size=image_size, patch_size=4, dim=64, depth=2,
                  heads=4, num_classes=num_classes)
