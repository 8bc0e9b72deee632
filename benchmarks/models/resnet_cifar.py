"""CIFAR ResNet with basic blocks: analytic operations and the plain reference.

Follows the reference repository's ``utils/model.py`` (3x3 stem without
max-pool, stages of ``stage_blocks`` basic blocks at ``widths`` with strides
1, 2, 2, 2, a 1x1 projection shortcut where shape changes, every conv without
bias and followed by batch norm, global average pool, linear head). Sizes
come from the ``arch`` block of the configuration's file. Written against the
published description, not against ``tpu_dist/nn``: it only shares the
parameter tree's key names, because it is given the program's parameters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: batch-norm statistics are over the whole batch, so the reference cannot
#: take the batch in chunks
WHOLE_BATCH = True
BN_EPS = 1e-5  # torch.nn.BatchNorm2d default, as in the source


def _convs(arch):
    """Every convolution as (k, c_in, c_out, output side)."""
    side = int(arch["image_size"])
    widths, blocks = arch["widths"], arch["stage_blocks"]
    out = [(3, int(arch["num_channels"]), widths[0], side)]
    c_in = widths[0]
    for width, n_blocks, stride in zip(widths, blocks, (1, 2, 2, 2)):
        for b in range(n_blocks):
            s = stride if b == 0 else 1
            side //= s
            out.append((3, c_in, width, side))
            out.append((3, width, width, side))
            if s != 1 or c_in != width:
                out.append((1, c_in, width, side))
            c_in = width
    return out


def forward_macs_per_sample(arch) -> int:
    """Multiply-accumulates of one forward pass in convolutions and the
    head; batch norm, ReLU and pooling are not counted (the usual MFU
    accounting: operations the matrix unit has to do)."""
    macs = sum(k * k * ci * co * side * side for k, ci, co, side in _convs(arch))
    return macs + arch["widths"][-1] * int(arch["num_classes"])


def train_flops_per_sample(arch) -> float:
    """Forward plus backward: 2 FLOP a MAC, backward twice the forward.
    Recomputation is not counted."""
    return 6.0 * forward_macs_per_sample(arch)


# -- plain float32 reference --------------------------------------------------

def _conv(w, x, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _bn_train(p, x):
    mean = x.mean(axis=(0, 1, 2))
    var = jnp.square(x - mean).mean(axis=(0, 1, 2))  # biased, as torch normalizes
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _block(p, x, stride):
    y = jax.nn.relu(_bn_train(p["bn1"], _conv(p["conv1"]["w"], x, stride, 1)))
    y = _bn_train(p["bn2"], _conv(p["conv2"]["w"], y, 1, 1))
    if "sc_conv" in p:
        x = _bn_train(p["sc_bn"], _conv(p["sc_conv"]["w"], x, stride, 0))
    return jax.nn.relu(y + x)


def logits(arch, params, images):
    """Training-mode forward on float32 NHWC images."""
    y = jax.nn.relu(_bn_train(params["stem_bn"], _conv(params["stem_conv"]["w"], images, 1, 1)))
    for si, stride in enumerate((1, 2, 2, 2)):
        for bi, p in enumerate(params[f"stage{si + 1}"]):
            y = _block(p, y, stride if bi == 0 else 1)
    return y.mean(axis=(1, 2)) @ params["fc"]["w"] + params["fc"]["b"]
