"""Pipeline-parallel ViT: transformer blocks sharded into stages over a
``pipe`` mesh axis, microbatches streamed GPipe-style.

No reference counterpart (SURVEY §2.3: no PP anywhere). Design: the
embed/positional/head layers are small and stay replicated (computed on
every device); only the uniform transformer-block stack is pipelined —
each device owns ``depth / n_stages`` consecutive blocks, held as STACKED
arrays (leading block dim) so one ``P('pipe')`` spec shards them. A stage
runs its blocks with a ``lax.scan``; stage handoff is
``tpu_dist.parallel.pipeline.pipeline_apply``'s ``ppermute`` ring.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from tpu_dist.nn.vit import (
    ViTDef,
    _dense,
    _ln_apply,
    block_forward,
    check_pos_capacity,
    patchify,
    tp_block_forward,
)
from tpu_dist.parallel.pipeline import pipeline_apply, pipeline_apply_interleaved


@dataclass(frozen=True)
class ViTPipelineDef:
    """Same architecture as :class:`ViTDef` with blocks stored STACKED:
    every ``params["blocks"]`` leaf has a leading ``depth`` dim.

    ``interleave=v > 1`` (with ``pp_stages=S``) selects the interleaved
    virtual-stage schedule (``pipeline_apply_interleaved``): device ``d``
    owns the ``v`` non-adjacent virtual stages ``d, d+S, ...``, so the
    stacked block rows are stored DEVICE-MAJOR (all of device 0's chunks,
    then device 1's, ...) — one ``P('pipe')`` spec still shards them; the
    sequential (non-pp) path un-permutes back to logical depth order.
    """

    image_size: int = 32
    patch_size: int = 4
    dim: int = 64
    depth: int = 4
    heads: int = 4
    mlp_ratio: int = 4
    num_classes: int = 10
    interleave: int = 1
    pp_stages: int = 0  # required when interleave > 1 (layout needs S)

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    def _vit(self) -> ViTDef:
        return ViTDef(
            image_size=self.image_size, patch_size=self.patch_size, dim=self.dim,
            depth=self.depth, heads=self.heads, mlp_ratio=self.mlp_ratio,
            num_classes=self.num_classes,
        )

    def _storage_perm(self):
        """Block-row permutation logical → storage (device-major chunks).
        Identity when interleave == 1."""
        import numpy as np  # noqa: PLC0415

        if self.interleave <= 1:
            return None
        n, v = self.pp_stages, self.interleave
        if n <= 0:
            raise ValueError("interleave > 1 requires pp_stages (stage count)")
        if self.depth % (n * v):
            raise ValueError(
                f"depth {self.depth} must divide into pp_stages*interleave="
                f"{n * v} chunks"
            )
        bpc = self.depth // (n * v)  # blocks per chunk (virtual stage)
        rows = []
        for d in range(n):
            for k in range(v):
                j = k * n + d  # logical virtual-stage index
                rows.extend(range(j * bpc, (j + 1) * bpc))
        return np.asarray(rows)

    def init(self, key, dtype=jnp.float32):
        params, state = self._vit().init(key, dtype)
        blocks = params.pop("blocks")  # list of per-block dicts → stacked
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *blocks
        )
        perm = self._storage_perm()
        if perm is not None:
            stacked = jax.tree_util.tree_map(lambda a: a[perm], stacked)
        params["blocks"] = stacked
        return params, state

    def pp_param_specs(self, axis: str):
        """Blocks sharded on their stacked leading dim; rest replicated."""
        from jax.sharding import PartitionSpec as P  # noqa: PLC0415

        return {
            "patch": {"w": P(), "b": P()},
            "pos": P(),
            "blocks": jax.tree_util.tree_map(
                lambda _: P(axis), self._block_leaf_template()
            ),
            "ln_f": {"scale": P(), "bias": P()},
            "head": {"w": P(), "b": P()},
        }

    def tp_param_specs(self, axis: str):
        """Pure-TP layout for the stacked-block storage (``--tp`` without
        ``--pp``): Megatron column/row sharding on the weight dims, the
        stacked leading (depth) dim unsharded.  The sequential apply path
        runs the same TP block per stacked row."""
        from jax.sharding import PartitionSpec as P  # noqa: PLC0415

        blocks = {
            "ln1": {"scale": P(), "bias": P()},
            "qkv": {"w": P(None, None, axis), "b": P(None, axis)},
            "proj": {"w": P(None, axis, None), "b": P()},
            "ln2": {"scale": P(), "bias": P()},
            "mlp1": {"w": P(None, None, axis), "b": P(None, axis)},
            "mlp2": {"w": P(None, axis, None), "b": P()},
        }
        return {
            "patch": {"w": P(), "b": P()},
            "pos": P(),
            "blocks": blocks,
            "ln_f": {"scale": P(), "bias": P()},
            "head": {"w": P(), "b": P()},
        }

    def pp_tp_param_specs(self, pp_axis: str, tp_axis: str):
        """Megatron PP×TP layout: blocks sharded over ``pp_axis`` on the
        stacked leading (depth) dim AND over ``tp_axis`` on the Megatron
        dims — qkv/mlp1 column-sharded, proj/mlp2 row-sharded, norms and
        row-output biases replicated within the stage.  Embed/head stay
        replicated (small, computed everywhere), same as plain PP."""
        from jax.sharding import PartitionSpec as P  # noqa: PLC0415

        blocks = {
            "ln1": {"scale": P(pp_axis), "bias": P(pp_axis)},
            "qkv": {"w": P(pp_axis, None, tp_axis), "b": P(pp_axis, tp_axis)},
            "proj": {"w": P(pp_axis, tp_axis, None), "b": P(pp_axis)},
            "ln2": {"scale": P(pp_axis), "bias": P(pp_axis)},
            "mlp1": {"w": P(pp_axis, None, tp_axis), "b": P(pp_axis, tp_axis)},
            "mlp2": {"w": P(pp_axis, tp_axis, None), "b": P(pp_axis)},
        }
        return {
            "patch": {"w": P(), "b": P()},
            "pos": P(),
            "blocks": blocks,
            "ln_f": {"scale": P(), "bias": P()},
            "head": {"w": P(), "b": P()},
        }

    def _block_leaf_template(self):
        return {
            "ln1": {"scale": 0, "bias": 0},
            "qkv": {"w": 0, "b": 0},
            "proj": {"w": 0, "b": 0},
            "ln2": {"scale": 0, "bias": 0},
            "mlp1": {"w": 0, "b": 0},
            "mlp2": {"w": 0, "b": 0},
        }

    def patchify(self, x):
        return patchify(x, self.patch_size)

    # -- forward -------------------------------------------------------------

    def _embed(self, params, x):
        t = _dense(params["patch"], self.patchify(x))
        check_pos_capacity(t.shape[1], params["pos"], self.image_size, self.patch_size)
        return t + params["pos"][: t.shape[1]].astype(t.dtype)[None]

    def _stage_scan(self, stage_blocks, t, attn_impl=None, tp_axis=None):
        """Run this stage's stacked blocks sequentially.  With ``tp_axis``
        each block is the Megatron-TP block (qkv/mlp1 arrive column-sharded,
        proj/mlp2 row-sharded — one psum pair per block over the tp axis)."""
        if tp_axis is not None:
            from tpu_dist.parallel.tensor import tp_ops  # noqa: PLC0415

            copy_to_tp, reduce_from_tp = tp_ops(tp_axis)
            h_dim = self.dim // self.heads

            def body(h, blk):
                return tp_block_forward(
                    blk, h, h_dim, copy_to_tp, reduce_from_tp,
                    attn_impl=attn_impl,
                ), None
        else:

            def body(h, blk):
                return block_forward(blk, h, self.heads, attn_impl=attn_impl), None

        out, _ = lax.scan(body, t, stage_blocks)
        return out

    def _finish(self, params, t):
        t = _ln_apply(params["ln_f"], t)
        return _dense(params["head"], t.mean(axis=1))

    def apply(
        self,
        params,
        state,
        x,
        *,
        train: bool = False,
        axis_name: Optional[str] = None,  # contract parity (no BN)
        pp_axis: Optional[str] = None,
        tp_axis: Optional[str] = None,
        n_microbatches: int = 0,
        attn_impl: Optional[str] = None,
    ):
        """Without ``pp_axis``: sequential scan over all blocks (reference
        semantics). With ``pp_axis``: ``params["blocks"]`` arrives holding
        only THIS stage's blocks; the batch is split into ``n_microbatches``
        (default: the stage count) and streamed through the ring.
        ``tp_axis`` (Megatron PP×TP): each stage's blocks additionally
        arrive TP-sliced (place params with :meth:`pp_tp_param_specs`);
        the stage computation runs the TP block with its psum pair.
        """
        del axis_name
        t = self._embed(params, x)
        if pp_axis is None:
            blocks = params["blocks"]
            perm = self._storage_perm()
            if perm is not None:  # storage is device-major — restore logical
                import numpy as np  # noqa: PLC0415

                inv = np.argsort(perm)
                blocks = jax.tree_util.tree_map(lambda a: a[inv], blocks)
            t = self._stage_scan(blocks, t, attn_impl, tp_axis)
            return self._finish(params, t), state

        n_stages = lax.axis_size(pp_axis)
        if self.interleave > 1 and self.pp_stages != n_stages:
            raise ValueError(
                f"model laid out for pp_stages={self.pp_stages}, mesh has "
                f"{n_stages} pipeline stages"
            )
        m = n_microbatches or n_stages
        b = t.shape[0]
        if b % m:
            raise ValueError(f"batch {b} must divide into {m} microbatches")
        micro = t.reshape(m, b // m, *t.shape[1:])
        if self.interleave > 1:
            v = self.interleave
            # local shard rows = this device's v chunks, k-major
            chunks = jax.tree_util.tree_map(
                lambda a: a.reshape(v, a.shape[0] // v, *a.shape[1:]),
                params["blocks"],
            )
            outs = pipeline_apply_interleaved(
                lambda blocks, h: self._stage_scan(blocks, h, attn_impl, tp_axis),
                chunks,
                micro,
                pp_axis,
                n_stages,
                v,
            )
        else:
            outs = pipeline_apply(
                lambda blocks, h: self._stage_scan(blocks, h, attn_impl, tp_axis),
                params["blocks"],
                micro,
                pp_axis,
                n_stages,
            )
        t = outs.reshape(b, *t.shape[1:])
        return self._finish(params, t), state


def vit_pp_tiny(num_classes: int = 10, image_size: int = 32) -> ViTPipelineDef:
    return ViTPipelineDef(image_size=image_size, num_classes=num_classes)
