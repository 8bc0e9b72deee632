"""LFM2-MoE hybrid decoder (gated short convolutions, grouped-head causal
attention with q/k norms and rotary positions, a dense gated feed-forward, then
sparse layers of gated experts, a tied head): analytic operations, the work of
each kernel-like region, and the plain float32 reference.

Sizes come from the ``arch`` block of the configuration's file, which holds
the keys of the published ``config.json`` (``model_type: lfm2_moe``). Layer
``i`` is two pre-norm residual blocks, ``x <- x + op_i(RMSNorm(x))`` and then
``x <- x + ffn_i(RMSNorm(x))``: ``op_i`` a gated short convolution
(``layer_types[i] == "conv"``) or attention (``"full_attention"``), ``ffn_i``
the dense gated feed-forward for the first ``num_dense_layers`` layers and
the expert layer after them. The parameters arrive as one entry a block, in
depth order (``pattern``: ``C`` convolution, ``*`` attention, ``F`` dense
feed-forward, ``E`` experts). Then a final RMSNorm and the head, which is the
embedding read transposed.

The reference is straightforward ``jax.numpy`` in float32: the convolution is
three shifted products, the expert layer loops over the experts it is given
densely over every token, attention is a full softmax a block of queries at a
time. The ``jax.checkpoint`` calls change what is stored for the backward
pass, never what is computed. It computes in the dtype of the parameters it
is handed: the harness hands it float32, ``benchmarks/control.py`` bfloat16
for its lower-precision control.

Two things reach the reference through ``arch`` beside the published keys:
``experts_held = [first, count]``, the share of the routed experts that this
chip holds (the router still scores all ``published.num_experts``, and what
the absent experts would add is left out, here as in the program), and
``router_bias``, the selection bias (``expert_bias``) of each expert layer at
the step that is compared: model state, written here by the data kind after
it has balanced the router (``benchmarks/data/tokens.py``); absent, zero.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

WHOLE_BATCH = False  # no statistic crosses samples: the batch can be taken in chunks
INPUT_DTYPE = np.int32  # token ids
QUERY_BLOCK = 512  # queries whose scores exist at once
TOPK_EPS = 1e-6  # added to the chosen scores' sum (the published implementation's constant)


# -- sizes ----------------------------------------------------------------------

def pattern(arch) -> str:
    """One character a block, in depth order."""
    dense = int(arch["num_dense_layers"])
    return "".join(
        ("C" if kind == "conv" else "*") + ("F" if i < dense else "E")
        for i, kind in enumerate(arch["layer_types"])
    )


def sizes(arch):
    """The derived widths of one configuration."""
    d, heads = int(arch["hidden_size"]), int(arch["num_attention_heads"])
    return {
        "d": d, "taps": int(arch["conv_L_cache"]),
        "a_heads": heads, "a_kv": int(arch["num_key_value_heads"]), "a_dim": d // heads,
        "theta": float(arch["rope_parameters"]["rope_theta"]),
        "f_dense": int(arch["intermediate_size"]), "f": int(arch["moe_intermediate_size"]),
        "experts": int(arch["published"]["num_experts"]),
        "held": tuple(int(v) for v in arch["experts_held"]),
        "top_k": int(arch["num_experts_per_tok"]),
        "scaling": float(arch["routed_scaling_factor"]),
        "eps": float(arch["norm_eps"]),
        "vocab": int(arch["vocab_size"]), "seq": int(arch["seq_len"]),
        "pattern": pattern(arch),
    }


# -- analytic operations ---------------------------------------------------------

def attention_macs_per_token(arch) -> float:
    """Scores and weighted values over the causal half: a token sees
    (S + 1) / 2 keys on average."""
    z = sizes(arch)
    return 2.0 * z["a_heads"] * z["a_dim"] * (z["seq"] + 1) / 2.0


def forward_macs_per_token(arch) -> dict:
    """Multiply-accumulates of one token's forward pass in matrix
    multiplications, by part. Routed experts at the balanced share: a token's
    ``top_k`` choices fall on the held experts with probability held/experts.
    Norms, gates, the rotation, the convolution's taps and the embedding's
    gather are not counted."""
    z = sizes(arch)
    d = z["d"]
    conv = d * 3 * d + d * d
    qkv_o = 2 * d * z["a_heads"] * z["a_dim"] + 2 * d * z["a_kv"] * z["a_dim"]
    routed = z["top_k"] * 3.0 * d * z["f"] * z["held"][1] / z["experts"]
    pat = z["pattern"]
    return {
        "conv": pat.count("C") * float(conv),
        "attention": pat.count("*") * (qkv_o + attention_macs_per_token(arch)),
        "dense": pat.count("F") * 3.0 * d * z["f_dense"],
        "experts": pat.count("E") * (d * z["experts"] + routed),
        "head": float(d * z["vocab"]),
    }


def train_flops_per_sample(arch) -> float:
    """One sequence forward plus backward: 2 FLOP a MAC, backward twice the
    forward. Recomputation is not counted."""
    return 6.0 * sizes(arch)["seq"] * sum(forward_macs_per_token(arch).values())


# The work of the regions the program names with ``jax.named_scope``; each
# gives (operations, bytes) of one training step's forward and backward,
# counted from what the mathematics needs, whatever implements it. A roofline
# share is max(ops / peak, bytes / bandwidth) over the region's traced time.

def conv_work(arch, tokens: int):
    """``conv/short``: from the split of ``W_in``'s output to ``y``. A channel
    of a token forward: B * u, the taps' products and sums, C * w; backward
    twice that. Bytes (bf16): B, C, u read and y written forward; dy, B, C, u
    read and the three gradients written backward."""
    z = sizes(arch)
    layers = z["pattern"].count("C")
    per_channel = 3.0 * (2 + 2 * z["taps"] - 1)
    return (per_channel * z["d"] * tokens * layers,
            2.0 * (4 + 4 + 3) * z["d"] * tokens * layers)


def gmm_work(arch, live_rows: float):
    """``moe/experts``: the three grouped products over the rows that reached
    a held expert (``live_rows``, summed over the expert layers of a step: the
    program's counter); the held weights (bf16) read once forward and twice
    backward, the rows read and written at the model's width, gate, up and
    their product at the experts'."""
    z = sizes(arch)
    layers = z["pattern"].count("E")
    weights = layers * z["held"][1] * 3 * z["d"] * z["f"] * 2
    rows = live_rows * (2 * z["d"] + 3 * z["f"]) * 2
    return 6.0 * live_rows * 3 * z["d"] * z["f"], 3.0 * (weights + rows)


def attention_work(arch, sequences: int):
    """``attn/causal``: scores and weighted values over the causal half; q, k,
    v read and the output written (bf16) once forward, and those, the
    output's gradient and the three gradients once backward."""
    z = sizes(arch)
    layers = z["pattern"].count("*")
    tokens = sequences * z["seq"]
    qo = z["a_heads"] * z["a_dim"]
    kv = z["a_kv"] * z["a_dim"]
    bytes_fwd = 2 * (2 * qo + 2 * kv)
    return (6.0 * attention_macs_per_token(arch) * tokens * layers,
            (bytes_fwd + 2 * bytes_fwd + 2 * qo) * tokens * layers)


# -- plain float32 reference -----------------------------------------------------

def _rms(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _conv(z, p, h):
    """The gated short convolution on one sequence ``h [S, d]``: three
    shifted products, no bias, no activation."""
    s, taps = h.shape[0], z["taps"]
    b, c, u = jnp.split(h @ p["in_proj"], 3, axis=-1)
    v = jnp.pad(b * u, ((taps - 1, 0), (0, 0)))
    w = sum(p["conv_w"][j] * v[j:j + s] for j in range(taps))     # k_j v_{t - (taps-1) + j}
    return (c * w) @ p["out_proj"]


def rotate(z, x):
    """Rotary positions 0..S-1 on ``x [S, heads, D]`` over the whole head:
    channel ``i`` pairs with ``i + D/2``, angle ``t theta^(-i / (D/2))``."""
    s, dim = x.shape[0], x.shape[-1]
    inv = np.float32(z["theta"] ** (-np.arange(dim // 2) / (dim // 2)))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :].astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(z, p, h):
    """Causal softmax attention on one sequence, four query heads a
    key/value head; q and k normed per head, then rotated; a block of
    queries at a time."""
    s = h.shape[0]
    heads, kv, dim = z["a_heads"], z["a_kv"], z["a_dim"]
    q = rotate(z, _rms(p["q_norm"], (h @ p["wq"]).reshape(s, heads, dim), z["eps"]))
    k = rotate(z, _rms(p["k_norm"], (h @ p["wk"]).reshape(s, kv, dim), z["eps"]))
    q = q.reshape(s, kv, heads // kv, dim)
    v = (h @ p["wv"]).reshape(s, kv, dim)
    blk = min(QUERY_BLOCK, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not whole blocks of {blk} queries")

    @jax.checkpoint
    def block(args):
        q_b, start = args
        scores = jnp.einsum("qkgd,skd->kgqs", q_b, k) / math.sqrt(dim)
        q_pos = start + jnp.arange(blk)[:, None]
        scores = jnp.where(jnp.arange(s)[None, :] <= q_pos, scores, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(s // blk, blk, kv, heads // kv, dim),
                              jnp.arange(0, s, blk)))
    return out.reshape(s, heads * dim) @ p["wo"]


def _dense(z, p, h):
    return (jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])) @ p["w2"]


def route(z, router, bias, h):
    """Scores of all experts, the ``top_k`` chosen by score plus selection
    bias, and their weights (scores renormalised over the chosen, times the
    scaling factor)."""
    scores = jax.nn.sigmoid(h @ router)
    _, chosen = jax.lax.top_k(scores + bias, z["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = z["scaling"] * picked / (picked.sum(-1, keepdims=True) + TOPK_EPS)
    return scores, chosen, weights


def _experts(z, p, bias, h):
    """The routed sum over the chosen experts that are held, one expert at a
    time over every token (unrolled, as ``models/nemotron_h.py`` says why)."""
    first, count = z["held"]
    _, chosen, weights = route(z, p["router"], bias, h)
    out = jnp.zeros_like(h)
    for e in range(count):
        gate = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1).astype(h.dtype)
        expert = (jax.nn.silu(h @ p["w_gate"][e]) * (h @ p["w_up"][e])) @ p["w_down"][e]
        out = out + gate[:, None] * expert
    return out


def hidden(arch, params, tokens):
    """The final-norm hidden states ``[S, d]`` of one sequence of token ids."""
    z = sizes(arch)
    bias = jnp.asarray(
        arch.get("router_bias", np.zeros((z["pattern"].count("E"), z["experts"]))),
        jnp.float32,
    )
    x = params["embed"][tokens]
    e = 0
    for kind, p in zip(z["pattern"], params["layers"]):
        if kind == "E":
            f = functools.partial(_experts, z, bias=bias[e])
            e += 1
        else:
            f = functools.partial({"C": _conv, "*": _attention, "F": _dense}[kind], z)
        x = x + jax.checkpoint(lambda p, y, f=f: f(p=p, h=_rms(p["norm"], y, z["eps"])))(p, x)
    return _rms(params["norm_f"], x, z["eps"])


def logits(arch, params, tokens):
    """``[n, S] -> [n, S, vocab]``, a sequence at a time; the head is the
    embedding read transposed."""
    return jax.lax.map(lambda t: hidden(arch, params, t) @ params["embed"].T, tokens)


def loss_sum(arch, params, inputs, targets):
    """Sum over the chunk's sequences of each one's mean next-token
    cross-entropy over all its positions, so that the batch's mean is the
    mean over every position."""
    def one(args):
        logp = jax.nn.log_softmax(hidden(arch, params, args[0]) @ params["embed"].T, axis=-1)
        return -jnp.take_along_axis(logp, args[1][:, None], axis=-1).mean()

    return jax.lax.map(one, (inputs, targets)).sum()
