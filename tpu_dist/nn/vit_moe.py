"""ViT with Mixture-of-Experts FFN blocks — expert parallelism end to end.

No reference counterpart (SURVEY §2.3: no EP anywhere). Every transformer
block's dense MLP is replaced by a Switch-style top-1 MoE
(:class:`tpu_dist.parallel.expert.MoE`); under an ``expert`` mesh axis the
expert weights live sharded (``ep_param_specs``) and tokens are exchanged
with one ``all_to_all`` per block, per direction.

Functional contract matches :class:`ViTDef` (``init``/``apply`` with
``ep_axis`` instead of ``tp_axis``), so it slots into the same train step
through ``param_specs`` + a model kwarg.

Gradient note: no conjugate ops are needed inside the model — the block
input carries DATA (each device holds different tokens), not a replica, and
``apply_ep``'s ``all_to_all`` transposes into the exact reverse
``all_to_all``. The whole correction lives in the train step's per-leaf
reduction (``tpu_dist/train/step.py::_ep_grad_reduce``): expert-sharded
leaves ``pmean(data)/n_ep``, replicated leaves ``pmean(data, expert)``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from tpu_dist.nn import attention as attn_lib
from tpu_dist.nn.vit import (
    _dense,
    _dense_init,
    _ln_apply,
    _ln_init,
    check_pos_capacity,
    patchify,
)
from tpu_dist.parallel.expert import MoE


@dataclass(frozen=True)
class ViTMoEDef:
    image_size: int = 32
    patch_size: int = 4
    dim: int = 64
    depth: int = 2
    heads: int = 4
    n_experts: int = 8
    capacity_factor: float = 2.0
    top_k: int = 1  # experts per token (1 = Switch, 2 = GShard-style)
    num_classes: int = 10

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def moe(self) -> MoE:
        return MoE(self.n_experts, self.capacity_factor, self.top_k)

    def init(self, key, dtype=jnp.float32):
        keys = iter(jax.random.split(key, 8 + 4 * self.depth))
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
                    "moe": self.moe.init(next(keys), self.dim, 4 * self.dim),
                }
            )
        p["blocks"] = blocks
        p["ln_f"] = _ln_init(self.dim)
        p["head"] = _dense_init(next(keys), self.dim, self.num_classes)
        if dtype != jnp.float32:
            p = jax.tree_util.tree_map(lambda t: t.astype(dtype), p)
        return p, {}

    def ep_param_specs(self, axis: str):
        """Experts sharded on their leading dim; everything else replicated."""
        from jax.sharding import PartitionSpec as P  # noqa: PLC0415

        block = {
            "ln1": {"scale": P(), "bias": P()},
            "qkv": {"w": P(), "b": P()},
            "proj": {"w": P(), "b": P()},
            "ln2": {"scale": P(), "bias": P()},
            "moe": {"router": P(), "w_in": P(axis), "w_out": P(axis)},
        }
        return {
            "patch": {"w": P(), "b": P()},
            "pos": P(),
            "blocks": [dict(block) for _ in range(self.depth)],
            "ln_f": {"scale": P(), "bias": P()},
            "head": {"w": P(), "b": P()},
        }

    def patchify(self, x):
        return patchify(x, self.patch_size)

    def apply(
        self,
        params,
        state,
        x,
        *,
        train: bool = False,
        axis_name: Optional[str] = None,  # unused (no BN); contract parity
        ep_axis: Optional[str] = None,
        attn_impl: Optional[str] = None,
    ):
        """``ep_axis`` set: the batch arrives sharded over BOTH the data and
        expert axes (the expert axis doubles as a data axis everywhere
        outside the MoE), expert weights arrive sharded
        (:meth:`ep_param_specs`), and each block's MoE exchanges tokens with
        its expert owners via ``all_to_all``.

        Training returns the depth-averaged router load-balancing loss in
        the state dict (``{"moe_aux_loss": scalar}``) — the train step adds
        ``moe_aux_coef`` times it to the objective and drops the key before
        the state is stored."""
        del axis_name
        tokens = self.patchify(x)
        t = _dense(params["patch"], tokens)
        check_pos_capacity(t.shape[1], params["pos"], self.image_size, self.patch_size)
        t = t + params["pos"][: t.shape[1]].astype(t.dtype)[None]

        h_dim = self.dim // self.heads
        b, s = t.shape[:2]
        aux_total = jnp.zeros((), jnp.float32)
        for blk in params["blocks"]:
            y = _ln_apply(blk["ln1"], t)
            o = attn_lib.projected_attention(y, blk["qkv"], h_dim, impl=attn_impl)
            t = t + _dense(blk["proj"], o)

            y = _ln_apply(blk["ln2"], t)
            flat = y.reshape(b * s, self.dim)
            if ep_axis is None:
                out, aux = self.moe.apply_dense(blk["moe"], flat, with_aux=True)
            else:
                out, aux = self.moe.apply_ep(
                    blk["moe"]["router"],
                    blk["moe"]["w_in"],
                    blk["moe"]["w_out"],
                    flat,
                    ep_axis,
                    with_aux=True,
                )
            aux_total = aux_total + aux.astype(jnp.float32)
            t = t + out.reshape(b, s, self.dim)

        t = _ln_apply(params["ln_f"], t)
        logits = _dense(params["head"], t.mean(axis=1))
        if train:
            return logits, {"moe_aux_loss": aux_total / self.depth}
        return logits, state


def vit_moe_tiny(num_classes: int = 10, image_size: int = 32) -> ViTMoEDef:
    return ViTMoEDef(image_size=image_size, num_classes=num_classes)
