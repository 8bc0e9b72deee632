"""Nemotron-H hybrid decoder (Mamba-2 mixers, sparse expert layers, grouped-head
causal attention): analytic operations, the work of each kernel-like region,
and the plain float32 reference.

Sizes come from the ``arch`` block of the configuration's file, which holds
the keys of the published ``config.json`` (``model_type: nemotron_h``). The
layer at depth ``i`` is ``hybrid_override_pattern[i]``: ``M`` a Mamba-2 mixer,
``*`` attention, ``E`` an expert layer; every layer is a pre-norm residual
block ``x <- x + f_i(RMSNorm(x))``, then a final RMSNorm and an untied head.

The reference is straightforward ``jax.numpy`` in float32: the mixer's
recurrence runs token by token, the expert layer loops over the experts it is
given densely, attention is a full softmax a block of queries at a time. The
``jax.checkpoint`` calls change what is stored for the backward pass, never
what is computed (the 64 x 64 x 128 state is 2 MB a token). It computes in
the dtype of the parameters it is handed: the harness hands it float32,
``benchmarks/control.py`` bfloat16 for its lower-precision control.

Two things reach the reference through ``arch`` beside the published keys:
``experts_held = [first, count]``, the share of the routed experts that this
chip holds (the router still scores all ``published.n_routed_experts``, and
what the absent experts would add is left out, here as in the program), and
``router_bias``, the selection bias of each expert layer at the step that is
compared. The bias is model state, not a parameter, so it is not in the
``params`` the harness hands over: the data kind writes it here after it has
balanced the router (``benchmarks/data/tokens.py``); absent, it is zero.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

WHOLE_BATCH = False  # no statistic crosses samples: the batch can be taken in chunks
INPUT_DTYPE = np.int32  # token ids
SCAN_BLOCK = 128  # tokens whose states the backward pass keeps at once
QUERY_BLOCK = 512  # queries whose scores exist at once


# -- sizes ----------------------------------------------------------------------

def sizes(arch):
    """The derived widths of one configuration."""
    d = int(arch["hidden_size"])
    heads, p = int(arch["mamba_num_heads"]), int(arch["mamba_head_dim"])
    groups, n = int(arch["n_groups"]), int(arch["ssm_state_size"])
    inner = heads * p
    return {
        "d": d, "m_heads": heads, "m_p": p, "m_groups": groups, "m_n": n,
        "m_inner": inner, "m_conv": inner + 2 * groups * n,
        "m_in": 2 * inner + 2 * groups * n + heads,
        "conv_k": int(arch["conv_kernel"]), "chunk": int(arch["chunk_size"]),
        "a_heads": int(arch["num_attention_heads"]),
        "a_kv": int(arch["num_key_value_heads"]), "a_dim": int(arch["head_dim"]),
        "experts": int(arch["published"]["n_routed_experts"]),
        "held": tuple(int(v) for v in arch["experts_held"]),
        "top_k": int(arch["num_experts_per_tok"]),
        "f": int(arch["moe_intermediate_size"]),
        "f_shared": int(arch["moe_shared_expert_intermediate_size"]),
        "scaling": float(arch["routed_scaling_factor"]),
        "eps": float(arch["layer_norm_epsilon"]),
        "vocab": int(arch["vocab_size"]), "seq": int(arch["seq_len"]),
        "pattern": str(arch["hybrid_override_pattern"]),
    }


# -- analytic operations ---------------------------------------------------------

def scan_macs_per_token(arch) -> float:
    """The mixer's recurrence in its chunked form at ``chunk_size`` Q: inside
    a chunk the causal half of C B^T (a group) and of its product with x (a
    head), then a head's state built and read once a token."""
    z = sizes(arch)
    q = z["chunk"]
    half = (q + 1) / 2.0
    return (half * (z["m_groups"] * z["m_n"] + z["m_heads"] * z["m_p"])
            + 2.0 * z["m_heads"] * z["m_p"] * z["m_n"])


def attention_macs_per_token(arch) -> float:
    """Scores and weighted values over the causal half: a token sees
    (S + 1) / 2 keys on average."""
    z = sizes(arch)
    return 2.0 * z["a_heads"] * z["a_dim"] * (z["seq"] + 1) / 2.0


def forward_macs_per_token(arch) -> dict:
    """Multiply-accumulates of one token's forward pass in matrix
    multiplications, by part. Routed experts at the balanced share: a token's
    ``top_k`` choices fall on the held experts with probability held/experts.
    Norms, activations, the convolution (4 taps) and the embedding's gather
    are not counted."""
    z = sizes(arch)
    d = z["d"]
    mixer = d * z["m_in"] + z["m_inner"] * d + scan_macs_per_token(arch)
    qkv_o = 2 * d * z["a_heads"] * z["a_dim"] + 2 * d * z["a_kv"] * z["a_dim"]
    attention = qkv_o + attention_macs_per_token(arch)
    routed = z["top_k"] * 2.0 * d * z["f"] * z["held"][1] / z["experts"]
    expert = d * z["experts"] + 2 * d * z["f_shared"] + routed
    pat = z["pattern"]
    return {
        "mixers": pat.count("M") * mixer, "attention": pat.count("*") * attention,
        "experts": pat.count("E") * expert, "head": float(d * z["vocab"]),
    }


def train_flops_per_sample(arch) -> float:
    """One sequence forward plus backward: 2 FLOP a MAC, backward twice the
    forward. Recomputation is not counted."""
    return 6.0 * sizes(arch)["seq"] * sum(forward_macs_per_token(arch).values())


# The work of the regions the program names with ``jax.named_scope``; each
# gives (operations, bytes) of one training step's forward and backward over
# ``tokens`` tokens, counted from what the mathematics needs, whatever
# implements it. A roofline share is max(ops / peak, bytes / bandwidth) over
# the region's traced time.

def scan_work(arch, tokens: int):
    """``ssm/scan``: the chunked form's operations; x, B, C (bf16) and dt
    (float32) read and y (bf16) written once each way."""
    z = sizes(arch)
    layers = z["pattern"].count("M")
    per_token = 2 * (z["m_inner"] + 2 * z["m_groups"] * z["m_n"]) + 4 * z["m_heads"] + 2 * z["m_inner"]
    return 6.0 * scan_macs_per_token(arch) * tokens * layers, 2.0 * per_token * tokens * layers


def gmm_work(arch, live_rows: float):
    """``moe/experts``: the two grouped products over the rows that reached a
    held expert (``live_rows``, summed over the expert layers of a step: the
    program's counter); the held weights (bf16) read once forward and twice
    backward, the rows read and written at both widths."""
    z = sizes(arch)
    layers = z["pattern"].count("E")
    weights = layers * z["held"][1] * 2 * z["d"] * z["f"] * 2
    rows = live_rows * (2 * z["d"] + 2 * z["f"]) * 2
    return 6.0 * live_rows * 2 * z["d"] * z["f"], 3.0 * (weights + rows)


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


def _mixer(z, p, h):
    """Mamba-2 on one sequence ``h [S, d]``, the recurrence token by token."""
    s = h.shape[0]
    heads, hp, groups, n = z["m_heads"], z["m_p"], z["m_groups"], z["m_n"]
    inner = z["m_inner"]
    proj = h @ p["in_proj"]
    gate, xbc, dt = jnp.split(proj, [inner, inner + z["m_conv"]], axis=-1)
    k = z["conv_k"]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(padded[i:i + s] * p["conv_w"][i] for i in range(k)) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    x, b, c = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    x = x.reshape(s, heads, hp)
    # eight heads share a group's B and C
    b = jnp.repeat(b.reshape(s, groups, n), heads // groups, axis=1)
    c = jnp.repeat(c.reshape(s, groups, n), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # [S, heads]
    a = -jnp.exp(p["A_log"])                                    # [heads]

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(token, state, inp)

    blk = min(SCAN_BLOCK, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not whole blocks of {blk} tokens")
    cut = lambda t: t.reshape((s // blk, blk) + t.shape[1:])  # noqa: E731
    _, y = jax.lax.scan(block, jnp.zeros((heads, hp, n), x.dtype),
                        (cut(x), cut(b), cut(c), cut(dt)))
    y = y.reshape(s, heads, hp) + p["D"][:, None] * x
    y = y.reshape(s, inner) * jax.nn.silu(gate)                 # gate before the norm
    y = _rms(1.0, y.reshape(s, groups, inner // groups), z["eps"]).reshape(s, inner)
    return (y * p["gnorm"]) @ p["out_proj"]


def _attention(z, p, h):
    """Causal softmax attention on one sequence, 16 query heads a key/value
    head, no positional encoding, a block of queries at a time."""
    s = h.shape[0]
    heads, kv, dim = z["a_heads"], z["a_kv"], z["a_dim"]
    q = (h @ p["wq"]).reshape(s, kv, heads // kv, dim)
    k = (h @ p["wk"]).reshape(s, kv, dim)
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


def route(z, router, bias, h):
    """Scores of all experts, the ``top_k`` chosen by score plus selection
    bias, and their weights (scores renormalised over the chosen, times the
    scaling factor)."""
    scores = jax.nn.sigmoid(h @ router)
    _, chosen = jax.lax.top_k(scores + bias, z["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = z["scaling"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return scores, chosen, weights


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _experts(z, p, bias, h):
    """The routed sum over the chosen experts that are held, one expert at a
    time over every token, plus the shared expert."""
    first, count = z["held"]
    _, chosen, weights = route(z, p["router"], bias, h)
    out = _relu2(h @ p["shared_up"]) @ p["shared_down"]
    # unrolled on purpose: a lax.scan over the experts compiles in a third of
    # the time (13 against 46 s a layer for the v5e) but XLA then wants 7.2 GiB
    # of temporaries for the whole reference instead of 5.4, and 5.4 is what
    # fits beside the trainer's state (compile-only, PR 33)
    for e in range(count):
        gate = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        out = out + gate[:, None] * (_relu2(h @ p["w_up"][e]) @ p["w_down"][e])
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
        if kind == "M":
            f = functools.partial(_mixer, z)
        elif kind == "*":
            f = functools.partial(_attention, z)
        else:
            f = functools.partial(_experts, z, bias=bias[e])
            e += 1
        x = x + jax.checkpoint(lambda p, y, f=f: f(p=p, h=_rms(p["norm"], y, z["eps"])))(p, x)
    return _rms(params["norm_f"], x, z["eps"])


def logits(arch, params, tokens):
    """``[n, S] -> [n, S, vocab]``, a sequence at a time."""
    return jax.lax.map(lambda t: hidden(arch, params, t) @ params["head"], tokens)


def loss_sum(arch, params, inputs, targets):
    """Sum over the chunk's sequences of each one's mean next-token
    cross-entropy over all its positions, so that the batch's mean is the
    mean over every position."""
    def one(args):
        logp = jax.nn.log_softmax(hidden(arch, params, args[0]) @ params["head"], axis=-1)
        return -jnp.take_along_axis(logp, args[1][:, None], axis=-1).mean()

    return jax.lax.map(one, (inputs, targets)).sum()
