"""Shared test fixtures: tiny models that compile fast on the emulated mesh."""

from __future__ import annotations

import jax

from tpu_dist.nn import layers as L
from tpu_dist.nn.resnet import ResNetDef


def tiny_resnet(num_classes: int = 10) -> ResNetDef:
    """Reference ResNet topology at 1/8 width — same code paths, ~40x fewer
    FLOPs, seconds to compile on the 8-device CPU mesh."""
    return ResNetDef("basic", (1, 1, 1, 1), num_classes, widths=(8, 8, 16, 16))


class TinyConvNet:
    """conv+bn+fc micro-model exercising every layer primitive."""

    def __init__(self, num_classes: int = 10, width: int = 8):
        self.num_classes = num_classes
        self.width = width

    def init(self, key):
        k1, k2 = jax.random.split(key)
        params = {"conv": L.conv_init(k1, 3, self.width, 3)}
        params["bn"], bn_state = L.bn_init(self.width)
        params["fc"] = L.linear_init(k2, self.width, self.num_classes)
        return params, {"bn": bn_state}

    def apply(self, params, state, x, *, train=False, axis_name=None):
        y = L.conv_apply(params["conv"], x, 1, 1)
        y, ns = L.bn_apply(params["bn"], state["bn"], y, train=train, axis_name=axis_name)
        y = L.relu(y)
        y = L.global_avg_pool(y)
        return L.linear_apply(params["fc"], y), {"bn": ns}


class TinyMLP:
    """BN-free model: exact arithmetic equivalence tests (grad accum, DP)."""

    def __init__(self, num_classes: int = 10, width: int = 16, in_dim: int = 12):
        self.num_classes = num_classes
        self.width = width
        self.in_dim = in_dim

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {
            "l1": L.linear_init(k1, self.in_dim, self.width),
            "l2": L.linear_init(k2, self.width, self.num_classes),
        }, {}

    def apply(self, params, state, x, *, train=False, axis_name=None):
        x = x.reshape(x.shape[0], -1)
        y = L.relu(L.linear_apply(params["l1"], x))
        return L.linear_apply(params["l2"], y), state


def hybrid_arch(m, bias=None) -> dict:
    """The ``arch`` block the plain reference (``benchmarks/models/nemotron_h.py``)
    reads, for a ``HybridDecoderDef``; ``bias``: the expert layers' selection bias."""
    arch = dict(
        hidden_size=m.hidden, mamba_num_heads=m.mamba_heads, mamba_head_dim=m.mamba_head_dim,
        n_groups=m.ssm_groups, ssm_state_size=m.ssm_state, conv_kernel=m.conv_kernel,
        chunk_size=m.chunk_size, num_attention_heads=m.attn_heads,
        num_key_value_heads=m.kv_heads, head_dim=m.attn_head_dim,
        published={"n_routed_experts": m.n_experts}, experts_held=list(m.experts_held),
        num_experts_per_tok=m.top_k, moe_intermediate_size=m.expert_width,
        moe_shared_expert_intermediate_size=m.shared_width,
        routed_scaling_factor=m.routed_scaling, layer_norm_epsilon=m.eps,
        vocab_size=m.vocab_size, seq_len=m.seq_len, hybrid_override_pattern=m.pattern,
    )
    if bias is not None:
        import numpy as np  # noqa: PLC0415

        arch["router_bias"] = np.asarray(bias)
    return arch


def lfm2_arch(m, bias=None) -> dict:
    """The ``arch`` block the plain reference (``benchmarks/models/lfm2_moe.py``)
    reads, for a ``HybridDecoderDef`` of LFM2 blocks (a layer is an operator
    block, ``C`` or ``*``, then a feed-forward block, ``F`` or ``E``)."""
    ops, ffns = m.pattern[0::2], m.pattern[1::2]
    arch = dict(
        hidden_size=m.hidden, conv_L_cache=m.conv_kernel, intermediate_size=m.dense_width,
        moe_intermediate_size=m.expert_width, num_attention_heads=m.attn_heads,
        num_key_value_heads=m.kv_heads, rope_parameters={"rope_theta": m.rope_theta},
        norm_eps=m.eps, num_experts_per_tok=m.top_k, routed_scaling_factor=m.routed_scaling,
        layer_types=["conv" if c == "C" else "full_attention" for c in ops],
        num_dense_layers=ffns.count("F"), published={"num_experts": m.n_experts},
        experts_held=list(m.experts_held), vocab_size=m.vocab_size, seq_len=m.seq_len,
    )
    if bias is not None:
        import numpy as np  # noqa: PLC0415

        arch["router_bias"] = np.asarray(bias)
    return arch
