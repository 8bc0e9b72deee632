"""CIFAR-style ResNet-18/34/50 (TPU-native re-design of ``utils/model.py``).

Architecture parity with the reference (``utils/model.py:61-127``):
3×3 stem without maxpool (CIFAR variant, ``:66-70``), stages
[64,128,256,512] with strides [1,2,2,2] (``:72-75``), BasicBlock
(expansion 1, ``:3-28``) for 18/34, BottleNeck (expansion 4, ``:32-59``)
for 50, global average pool + linear head (``:76-77``), 100 classes by
default (``:62``). Every conv is bias-free and followed by BatchNorm — the
property that makes SyncBN a real requirement.

Differences from the reference are layout-only: NHWC tensors, functional
``init``/``apply`` over pytree dicts (see ``tpu_dist.nn.layers``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_dist.nn import layers as L
from tpu_dist.obs import hlo_scopes


# one literal a stage, so that the table in obs/hlo_scopes.py and the test that
# holds the sites to it can read them
_STAGE_SCOPES = (
    lambda: hlo_scopes.scope("resnet/stage1"),
    lambda: hlo_scopes.scope("resnet/stage2"),
    lambda: hlo_scopes.scope("resnet/stage3"),
    lambda: hlo_scopes.scope("resnet/stage4"),
)


@dataclass(frozen=True)
class ResNetDef:
    """Static model description; ``init``/``apply`` close over it.

    ``widths`` defaults to the reference's stage widths
    (``utils/model.py:72-75``); narrower widths give the test suite a
    fast-compiling miniature with identical code paths.
    """

    block: str  # "basic" | "bottleneck"
    stage_blocks: Tuple[int, int, int, int]
    num_classes: int = 100
    widths: Tuple[int, int, int, int] = (64, 128, 256, 512)
    # CIFAR variant (reference default): 3x3 stem, no maxpool
    # (utils/model.py:66-70). imagenet_stem=True switches to the canonical
    # 7x7/stride-2 stem + 3x3/stride-2 maxpool for 224x224 inputs.
    imagenet_stem: bool = False
    # MXU-friendly stem (TPU-only concern, MLPerf-style): compute the
    # 7x7/2 stem as a mathematically-identical 4x4/1 conv on the 2x2
    # space-to-depth transform of the input. C_in=3 leaves 125 of the
    # MXU's 128 input lanes idle for the heaviest-spatial conv of the
    # net; s2d quadruples arithmetic intensity (C_in 3→12, spatial /4)
    # without changing parameters, checkpoints, or numerics (bit-exact
    # up to f32 summation order — see tests/test_models.py). Only
    # meaningful with imagenet_stem; requires even H, W.
    s2d_stem: bool = False

    @property
    def expansion(self) -> int:
        return 1 if self.block == "basic" else 4

    # -- init ---------------------------------------------------------------

    def init(self, key, dtype=jnp.float32):
        """Returns ``(params, bn_state)`` pytrees (nested dicts/lists)."""
        keys = iter(jax.random.split(key, 1024))
        params = {}
        state = {}

        stem = self.widths[0]
        stem_k = 7 if self.imagenet_stem else 3
        params["stem_conv"] = L.conv_init(next(keys), 3, stem, stem_k, dtype)
        params["stem_bn"], state["stem_bn"] = L.bn_init(stem, dtype)

        in_ch = stem
        for si, (width, n_blocks, stride) in enumerate(
            zip(self.widths, self.stage_blocks, (1, 2, 2, 2))
        ):
            blocks_p: List[dict] = []
            blocks_s: List[dict] = []
            for bi in range(n_blocks):
                s = stride if bi == 0 else 1
                p, st, in_ch = self._block_init(next(keys), in_ch, width, s, dtype)
                blocks_p.append(p)
                blocks_s.append(st)
            params[f"stage{si + 1}"] = blocks_p
            state[f"stage{si + 1}"] = blocks_s

        params["fc"] = L.linear_init(
            next(keys), self.widths[-1] * self.expansion, self.num_classes, dtype
        )
        return params, state

    def _block_init(self, key, in_ch, width, stride, dtype):
        out_ch = width * self.expansion
        ks = iter(jax.random.split(key, 8))
        p, s = {}, {}
        if self.block == "basic":
            p["conv1"] = L.conv_init(next(ks), in_ch, width, 3, dtype)
            p["bn1"], s["bn1"] = L.bn_init(width, dtype)
            p["conv2"] = L.conv_init(next(ks), width, out_ch, 3, dtype)
            p["bn2"], s["bn2"] = L.bn_init(out_ch, dtype)
        else:
            p["conv1"] = L.conv_init(next(ks), in_ch, width, 1, dtype)
            p["bn1"], s["bn1"] = L.bn_init(width, dtype)
            p["conv2"] = L.conv_init(next(ks), width, width, 3, dtype)
            p["bn2"], s["bn2"] = L.bn_init(width, dtype)
            p["conv3"] = L.conv_init(next(ks), width, out_ch, 1, dtype)
            p["bn3"], s["bn3"] = L.bn_init(out_ch, dtype)
        if stride != 1 or in_ch != out_ch:
            p["sc_conv"] = L.conv_init(next(ks), in_ch, out_ch, 1, dtype)
            p["sc_bn"], s["sc_bn"] = L.bn_init(out_ch, dtype)
        return p, s, out_ch

    # -- apply --------------------------------------------------------------

    def apply(
        self,
        params,
        state,
        x,
        *,
        train: bool = False,
        axis_name: Optional[str] = None,
    ):
        """Forward pass. ``x``: NHWC. Returns ``(logits, new_bn_state)``.

        ``axis_name`` enables SyncBatchNorm over that mesh axis (reference
        ``distributed.py:59`` semantics); only meaningful when ``train``.
        """
        bn = dict(train=train, axis_name=axis_name)
        new_state = {}

        with hlo_scopes.scope("resnet/stem"):
            if self.imagenet_stem:
                if self.s2d_stem:
                    y = self._stem_s2d(params["stem_conv"]["w"], x)
                else:
                    y = L.conv_apply(params["stem_conv"], x, stride=2, padding=3)
            else:
                y = L.conv_apply(params["stem_conv"], x, stride=1, padding=1)
            y, new_state["stem_bn"] = L.bn_apply(params["stem_bn"], state["stem_bn"], y, **bn)
            y = L.relu(y)
            if self.imagenet_stem:
                y = jax.lax.reduce_window(
                    y, -jnp.inf, jax.lax.max,
                    (1, 3, 3, 1), (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)],
                )

        for si, stage in enumerate(_STAGE_SCOPES):
            name = f"stage{si + 1}"
            stage_state = []
            with stage():
                for bp, bs in zip(params[name], state[name]):
                    stride = (1, 2, 2, 2)[si] if not stage_state else 1
                    y, ns = self._block_apply(bp, bs, y, stride, bn)
                    stage_state.append(ns)
            new_state[name] = stage_state

        with hlo_scopes.scope("resnet/head"):
            y = L.global_avg_pool(y)
            logits = L.linear_apply(params["fc"], y)
        return logits, new_state

    @staticmethod
    def _stem_s2d(w, x):
        """7x7/stride-2 stem conv, computed as an equivalent 4x4/stride-1
        conv over the 2x2 space-to-depth rearrangement of the input.

        Identity: pad the kernel to 8x8 with a zero top row/left column,
        so ``y[i,j] = Σ_{a,b∈[0,8)} W8[a,b]·x[2i+a-4, 2j+b-4]``; split
        ``a = 2p+u`` (phase u over the s2d factor) and the sum factorizes
        into a 4x4 conv over ``X[m,n,(u,v,c)] = x[2m+u, 2n+v, c]`` with
        asymmetric padding (2,1). Parameters stay stored as the plain
        [7,7,3,C] kernel — checkpoints are interchangeable between the
        two stems; the rearrangement is ~9k elements at trace time.
        """
        from jax import lax as _lax  # noqa: PLC0415

        k, _, c_in, c_out = w.shape
        if k != 7:
            raise ValueError(f"s2d stem expects the 7x7 kernel, got {k}x{k}")
        w8 = jnp.pad(w, ((1, 0), (1, 0), (0, 0), (0, 0)))
        w4 = (
            w8.reshape(4, 2, 4, 2, c_in, c_out)
            .transpose(0, 2, 1, 3, 4, 5)
            .reshape(4, 4, 4 * c_in, c_out)
        )
        n, h, wd, c = x.shape
        if h % 2 or wd % 2:
            raise ValueError(f"s2d stem needs even H, W; got {h}x{wd}")
        xs = (
            x.reshape(n, h // 2, 2, wd // 2, 2, c)
            .transpose(0, 1, 3, 2, 4, 5)
            .reshape(n, h // 2, wd // 2, 4 * c)
        )
        return _lax.conv_general_dilated(
            xs,
            w4.astype(xs.dtype),
            window_strides=(1, 1),
            padding=[(2, 1), (2, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def _block_apply(self, p, s, x, stride, bn):
        ns = {}
        if self.block == "basic":
            y = L.conv_apply(p["conv1"], x, stride=stride, padding=1)
            y, ns["bn1"] = L.bn_apply(p["bn1"], s["bn1"], y, **bn)
            y = L.relu(y)
            y = L.conv_apply(p["conv2"], y, stride=1, padding=1)
            y, ns["bn2"] = L.bn_apply(p["bn2"], s["bn2"], y, **bn)
        else:
            y = L.conv_apply(p["conv1"], x, stride=1, padding=0)
            y, ns["bn1"] = L.bn_apply(p["bn1"], s["bn1"], y, **bn)
            y = L.relu(y)
            y = L.conv_apply(p["conv2"], y, stride=stride, padding=1)
            y, ns["bn2"] = L.bn_apply(p["bn2"], s["bn2"], y, **bn)
            y = L.relu(y)
            y = L.conv_apply(p["conv3"], y, stride=1, padding=0)
            y, ns["bn3"] = L.bn_apply(p["bn3"], s["bn3"], y, **bn)

        if "sc_conv" in p:
            sc = L.conv_apply(p["sc_conv"], x, stride=stride, padding=0)
            sc, ns["sc_bn"] = L.bn_apply(p["sc_bn"], s["sc_bn"], sc, **bn)
        else:
            sc = x
        return L.relu(y + sc), ns


def resnet18(num_classes: int = 100) -> ResNetDef:
    """Reference factory parity: ``utils/model.py:115-117``."""
    return ResNetDef("basic", (2, 2, 2, 2), num_classes)


def resnet34(num_classes: int = 100) -> ResNetDef:
    """Reference factory parity: ``utils/model.py:120-122``."""
    return ResNetDef("basic", (3, 4, 6, 3), num_classes)


def resnet50(num_classes: int = 100) -> ResNetDef:
    """Reference factory parity: ``utils/model.py:125-127``."""
    return ResNetDef("bottleneck", (3, 4, 6, 3), num_classes)


def resnet50_imagenet(num_classes: int = 1000, s2d_stem: bool = False) -> ResNetDef:
    """Canonical ImageNet ResNet-50 (7x7 stem + maxpool; ~25.6M params) —
    for the BASELINE ResNet-50/ImageNet-1k config. ``s2d_stem=True``
    computes the identical stem via space-to-depth (TPU MXU utilization;
    same params/checkpoints)."""
    return ResNetDef(
        "bottleneck", (3, 4, 6, 3), num_classes,
        imagenet_stem=True, s2d_stem=s2d_stem,
    )
