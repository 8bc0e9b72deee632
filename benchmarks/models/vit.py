"""Vision Transformer: analytic operations and the plain reference.

Sizes come from the ``arch`` block of the configuration's file, which holds
the keys of the published ``config.json`` (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``intermediate_size``,
``patch_size``, ``image_size``). Pre-norm blocks, softmax attention, two-layer
GELU MLP, final layer norm, linear head, as Dosovitskiy et al. describe.

Departures from the published model, all because the program under test
(``tpu_dist/nn/vit.py``) makes them and the reference is given its parameters:
no class token (the head reads the mean over patch tokens, so 196 tokens at
224 px, not 197); the tanh approximation of GELU; the patch embedding is a
dense layer over flattened patches (equal to the strided convolution); the
2304 columns of the fused q/k/v projection are laid out head by head as
``[heads, (q, k, v), head_dim]``. Each is listed in the configuration's file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

WHOLE_BATCH = False  # layer norm only: the batch can be taken in chunks
LN_EPS = 1e-6  # the original ViT; HF's config.json says 1e-12, see departures


def n_tokens(arch) -> int:
    return (int(arch["image_size"]) // int(arch["patch_size"])) ** 2


def forward_macs_per_sample(arch, tokens=None) -> int:
    """Multiply-accumulates of one forward pass in matrix multiplications:
    patch embedding, q/k/v, scores, weighted values, output projection, MLP,
    head. Layer norm, softmax, GELU and residuals are not counted."""
    s = n_tokens(arch) if tokens is None else int(tokens)
    d, f = int(arch["hidden_size"]), int(arch["intermediate_size"])
    patch_dim = int(arch["patch_size"]) ** 2 * int(arch["num_channels"])
    per_layer = s * d * 3 * d + 2 * s * s * d + s * d * d + 2 * s * d * f
    return (s * patch_dim * d + int(arch["num_hidden_layers"]) * per_layer
            + d * int(arch["num_labels"]))


def train_flops_per_sample(arch) -> float:
    """Forward plus backward: 2 FLOP a MAC, backward twice the forward.
    Recomputation is not counted."""
    return 6.0 * forward_macs_per_sample(arch)


# -- plain float32 reference --------------------------------------------------

def _ln(p, x):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(p, x):
    return x @ p["w"] + p["b"]


def _block(arch, p, t):
    b, s, d = t.shape
    heads = int(arch["num_attention_heads"])
    qkv = _dense(p["qkv"], _ln(p["ln1"], t)).reshape(b, s, heads, 3, d // heads)
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d / heads)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    t = t + _dense(p["proj"], o.reshape(b, s, d))
    y = jax.nn.gelu(_dense(p["mlp1"], _ln(p["ln2"], t)), approximate=True)
    return t + _dense(p["mlp2"], y)


def logits(arch, params, images):
    """Forward on float32 NHWC images."""
    b, h, w, c = images.shape
    ps = int(arch["patch_size"])
    x = images.reshape(b, h // ps, ps, w // ps, ps, c).transpose(0, 1, 3, 2, 4, 5)
    t = _dense(params["patch"], x.reshape(b, (h // ps) * (w // ps), ps * ps * c))
    t = t + params["pos"][: t.shape[1]]
    for p in params["blocks"]:
        t = _block(arch, p, t)
    return _dense(params["head"], _ln(params["ln_f"], t).mean(axis=1))
