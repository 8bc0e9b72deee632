"""Hybrid decoders as a pattern of blocks: one definition, ``HybridDecoderDef``,
runs every token model of the framework. A pattern string names the blocks in
depth order, one pre-norm residual block a character, ``x <- x +
f_i(RMSNorm(x))``:

* ``M`` Mamba-2 mixer, ``*`` grouped-head causal attention, ``E`` dropless
  expert layer (the Nemotron-H family, ``model_type: nemotron_h``: non-gated
  ``relu^2`` experts beside a shared expert, no positions, an untied head);
* ``C`` gated short convolution, ``F`` dense gated feed-forward (the LFM2
  family, ``model_type: lfm2_moe``, whose layer is two blocks: operator, then
  feed-forward; there ``*`` has per-head q/k RMSNorm and rotary positions,
  ``E`` has gated experts and no shared expert, and the head is the embedding
  read transposed).

What differs between families at one kind of block is a field of the
definition (``gated_experts``, ``shared_width`` 0, ``qk_norm``, ``rope_theta``,
``tied_head``, ``topk_eps``), each defaulting to the Nemotron form. Then a
final RMSNorm and the head. ``docs/hybrid_decoder.md`` has the equations.

The contract is wider than ``ViTDef``'s. ``init(key) -> (params, state)`` and
``apply(params, state, tokens, train=) -> (logits, state)`` as everywhere;
beside them ``loss(params, state, tokens, targets, train=, compute_dtype=)``,
which the train and eval steps call where a model has it: the head and its
cross-entropy run over the tokens in blocks, so no ``[tokens, vocabulary]``
array outlives a block, and blocks are recomputed in the backward pass one at
a time (``jax.checkpoint`` a block, not one around the whole loss; which
blocks, ``recompute`` says).

``state`` holds what is not a parameter: ``router_bias [expert layers,
experts]``, the selection bias of the auxiliary-loss-free balancing rule,
which moves by ``bias_rate * sign(mean load - load)`` after every training
step and receives no gradient.

Mixed precision is the model's own: parameters arrive in float32 and each
matrix is cast to ``compute_dtype`` where it is used; the router, the
mixer's decay and state, every norm, the rotation, the gates' products and
the loss stay in float32; the residual stream is in ``compute_dtype``.

A deployment's share: ``experts_held = (first, count)`` says which routed
experts this chip holds (the experts' matrices have ``count`` leading rows);
the router scores all ``n_experts``, and the layer adds only what its own
experts give (to the shared expert's output, where there is one).
``vocab_size`` is the slice of the vocabulary held here.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpu_dist.nn import attention as attn_lib
from tpu_dist.nn import functional as F
from tpu_dist.obs import counters as counters_lib
from tpu_dist.obs import hlo_scopes
from tpu_dist.parallel import expert as expert_lib


def rms_norm(scale, x, eps: float):
    """RMSNorm over the last axis in float32, back in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def takes_scan_kernel(chunk: int, heads_per_group: int, head_dim: int, state: int,
                      dtype, state_dtype) -> bool:
    """Where :func:`ssm_scan` takes the Pallas kernel pair: a TPU, float32
    decays and state (the kernel has no other), and a shape its blocks fit."""
    if not _on_tpu() or jnp.dtype(state_dtype) != jnp.float32:
        return False
    from tpu_dist.ops.ssm_scan import fits  # noqa: PLC0415

    return fits(chunk, heads_per_group, head_dim, state, dtype)


def takes_conv_kernel(seq: int, borders, taps: int, dtype) -> bool:
    """Where the mixer's convolution takes the Pallas kernel pair of
    ``ops/causal_conv1d.py``: a TPU, and sequences, column borders of the
    convolved sections in the projection, taps and a dtype its blocks fit."""
    if not _on_tpu():
        return False
    from tpu_dist.ops.causal_conv1d import fits  # noqa: PLC0415

    return fits(seq, borders, taps, dtype)


def ssm_scan(x, dt, a, b, c, chunk: int, state_dtype=jnp.float32):
    """Mamba-2's recurrence ``H_t = exp(dt_t a) H_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = H_t C_t`` in chunks of ``chunk`` tokens: the quadratic form inside
    a chunk, one state a chunk carried between them.

    ``x [B,S,H,P]``, ``dt [B,S,H]`` (float32, after softplus), ``a [H]``
    (float32, negative), ``b``/``c [B,S,G,N]`` (``H/G`` heads share a group's
    B and C). Decays and the carried state are ``state_dtype``: float32 in
    the model (the benchmark's lower-precision control passes bfloat16 to show
    what that costs); the four products take their operands in ``x``'s dtype
    and accumulate in float32. Returns ``y [B,S,H,P]`` in ``x``'s dtype.

    One algorithm, two realisations, chosen by what is seen here: on a TPU,
    with float32 state and shapes the kernel pair of ``ops/ssm_scan.py``
    takes (:func:`takes_scan_kernel`), the chunks are walked in order with
    the carried state in VMEM (counted in ``ssm.sites_kernel``); anything
    else is the einsum form below, differentiable by plain autodiff
    (``ssm.sites_xla``), which puts every chunk's state through HBM."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    e = h // g
    if s % chunk:
        raise ValueError(f"sequence {s} is not whole chunks of {chunk} tokens")
    if takes_scan_kernel(chunk, e, p, n, x.dtype, state_dtype):
        from tpu_dist.ops import ssm_scan as scan_kernel  # noqa: PLC0415

        counters_lib.inc("ssm.sites_kernel")
        return scan_kernel.ssm_scan(x, dt, a, b, c, chunk, interpret=False)  # only on a TPU
    counters_lib.inc("ssm.sites_xla")
    nc, dtype, f32, sd = s // chunk, x.dtype, jnp.float32, state_dtype
    xr = x.reshape(bsz, nc, chunk, g, e, p)
    br = b.reshape(bsz, nc, chunk, g, n)
    cr = c.reshape(bsz, nc, chunk, g, n)
    dtr = dt.reshape(bsz, nc, chunk, g, e)
    cum = jnp.cumsum((dtr * a.reshape(g, e)).astype(sd), axis=2)  # [B,nc,Q,G,E], <= 0
    xdt = xr.astype(f32) * dtr[..., None]                       # dt_s x_s

    # inside a chunk: y_q += sum_{s<=q} exp(cum_q - cum_s) (C_q . B_s) dt_s x_s
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", cr, br, preferred_element_type=f32)
    seg = cum.transpose(0, 1, 3, 4, 2)                          # [B,nc,G,E,Q]
    seg = seg[..., :, None] - seg[..., None, :]                 # cum_q - cum_s
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    m = cb[:, :, :, None] * jnp.exp(jnp.where(causal, seg, -jnp.inf))
    y = jnp.einsum("bcgeqs,bcsgep->bcqgep", m.astype(dtype), xdt.astype(dtype),
                   preferred_element_type=f32)

    # a chunk's own state at its end, then the state each chunk starts from
    last = cum[:, :, -1]                                        # [B,nc,G,E]
    to_end = jnp.exp(last[:, :, None] - cum)
    states = jnp.einsum("bcsgep,bcsgn->bcgepn", (xdt * to_end[..., None]).astype(dtype),
                        br, preferred_element_type=sd)
    before = jnp.cumsum(last, axis=1) - last                    # sum of last_k, k < c
    after = before + last                                       # k <= c
    between = before[:, :, None] - after[:, None, :]            # [B,c,z,G,E]: z < k < c
    earlier = jnp.tril(jnp.ones((nc, nc), bool), k=-1)[None, :, :, None, None]
    carry = jnp.exp(jnp.where(earlier, between, -jnp.inf))
    start = jnp.einsum("bczge,bzgepn->bcgepn", carry, states,
                       precision=lax.Precision.HIGHEST)
    y = y + jnp.einsum("bcqgn,bcgepn->bcqgep", cr, start.astype(dtype),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    return y.reshape(bsz, s, h, p).astype(dtype)


@dataclass(frozen=True)
class HybridDecoderDef:
    pattern: str
    vocab_size: int
    seq_len: int
    hidden: int
    mamba_heads: int
    mamba_head_dim: int
    ssm_groups: int
    ssm_state: int
    conv_kernel: int                     # taps of the depthwise causal convolution (`M`, `C`)
    chunk_size: int
    attn_heads: int
    kv_heads: int
    attn_head_dim: int
    n_experts: int                       # the router's width
    experts_held: Tuple[int, int]        # (first, count) of the routed experts held here
    top_k: int
    expert_width: int
    shared_width: int                    # 0: no shared expert
    routed_scaling: float = 2.5
    eps: float = 1e-5
    bias_rate: float = 1e-3
    # rows of the dropless buffer over the balanced share: a router at its
    # random initialisation sent up to 1.7x (docs/hybrid_decoder.md)
    capacity_factor: float = 2.0
    head_block: int = 2048               # tokens whose logits exist at once
    # the layers (by depth) that training recomputes in the backward pass; a
    # layer left out keeps its activations instead (memory for time). None: all
    recompute: Optional[Tuple[int, ...]] = None
    rescale_layers: Optional[int] = None  # depth the residual outputs' init is scaled for
    # what a family says of its blocks; the defaults are Nemotron-H's
    dense_width: int = 0                 # an `F` block's width
    gated_experts: bool = False          # (silu(x W_gate) * (x W_up)) W_down, not relu2(x W_up) W_down
    qk_norm: bool = False                # per-head RMSNorm of q and k, learned weights
    rope_theta: Optional[float] = None   # rotary base over the whole head; None: no positions
    tied_head: bool = False              # the head is the embedding read transposed
    topk_eps: float = 1e-20              # added to the chosen scores' sum (LFM2 publishes 1e-6)

    # -- sizes -----------------------------------------------------------------

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def n_expert_layers(self) -> int:
        return self.pattern.count("E")

    def buffer_rows(self, tokens: int) -> int:
        """Static rows of an expert layer's buffer for ``tokens`` tokens."""
        balanced = tokens * self.top_k * self.experts_held[1] / self.n_experts
        return min(tokens * self.top_k, int(math.ceil(self.capacity_factor * balanced / 8.0)) * 8)

    # -- parameters ------------------------------------------------------------

    def init(self, key, dtype=jnp.float32):
        """``(params, state)`` from ``key``, as one compiled program: run
        eagerly, each of the ~50 leaves' draws compiles a program of its own,
        50 s of a cold start on the v5e (my chip run, PR 33)."""
        return _jitted_init(self, key, dtype)

    def _init(self, key, dtype):
        d, std = self.hidden, 0.02
        out_std = std / math.sqrt(self.rescale_layers or len(self.pattern))
        normal = lambda k, shape, s=std: (jax.random.normal(k, shape) * s).astype(dtype)  # noqa: E731
        keys = jax.random.split(key, len(self.pattern) + 2)
        layers = []
        for kind, k in zip(self.pattern, keys):
            ks = jax.random.split(k, 8)
            p = {"norm": jnp.ones((d,), dtype)}
            if kind == "M":
                h, inner = self.mamba_heads, self.mamba_inner
                bound = self.conv_kernel ** -0.5
                step = jnp.exp(jax.random.uniform(
                    ks[4], (h,), minval=math.log(1e-3), maxval=math.log(1e-1)))
                step = jnp.maximum(step, 1e-4)
                p.update(
                    in_proj=normal(ks[0], (d, 2 * inner + 2 * self.ssm_groups * self.ssm_state + h)),
                    conv_w=jax.random.uniform(ks[1], (self.conv_kernel, self.conv_dim), dtype, -bound, bound),
                    conv_b=jax.random.uniform(ks[2], (self.conv_dim,), dtype, -bound, bound),
                    A_log=jnp.log(jax.random.uniform(ks[3], (h,), dtype, 1.0, 16.0)),
                    dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(dtype),  # softplus^-1
                    D=jnp.ones((h,), dtype),
                    gnorm=jnp.ones((inner,), dtype),
                    out_proj=normal(ks[5], (inner, d), out_std),
                )
            elif kind == "*":
                q, kv = self.attn_heads * self.attn_head_dim, self.kv_heads * self.attn_head_dim
                p.update(wq=normal(ks[0], (d, q)), wk=normal(ks[1], (d, kv)),
                         wv=normal(ks[2], (d, kv)), wo=normal(ks[3], (q, d), out_std))
                if self.qk_norm:
                    p.update(q_norm=jnp.ones((self.attn_head_dim,), dtype),
                             k_norm=jnp.ones((self.attn_head_dim,), dtype))
            elif kind == "E":
                held, f, fs = self.experts_held[1], self.expert_width, self.shared_width
                p.update(
                    router=normal(ks[0], (d, self.n_experts)),
                    w_up=normal(ks[1], (held, d, f)), w_down=normal(ks[2], (held, f, d), out_std),
                )
                if fs:
                    p.update(shared_up=normal(ks[3], (d, fs)),
                             shared_down=normal(ks[4], (fs, d), out_std))
                if self.gated_experts:
                    p.update(w_gate=normal(ks[5], (held, d, f)))
            elif kind == "C":
                bound = self.conv_kernel ** -0.5
                p.update(
                    in_proj=normal(ks[0], (d, 3 * d)),          # [B | C | u]
                    conv_w=jax.random.uniform(ks[1], (self.conv_kernel, d), dtype, -bound, bound),
                    out_proj=normal(ks[2], (d, d), out_std),
                )
            elif kind == "F":
                f = self.dense_width
                p.update(w1=normal(ks[0], (d, f)), w3=normal(ks[1], (d, f)),
                         w2=normal(ks[2], (f, d), out_std))
            else:
                raise ValueError(f"pattern {self.pattern!r}: unknown layer kind {kind!r}")
            layers.append(p)
        params = {
            "embed": normal(keys[-2], (self.vocab_size, d)),
            "layers": layers,
            "norm_f": jnp.ones((d,), dtype),
        }
        if not self.tied_head:
            params["head"] = normal(keys[-1], (d, self.vocab_size))
        state = {"router_bias": jnp.zeros((self.n_expert_layers, self.n_experts), jnp.float32)}
        return params, state

    # -- layers ----------------------------------------------------------------

    def _mixer(self, p, h, dtype):
        bsz, s, _ = h.shape
        heads, hp, g, n = self.mamba_heads, self.mamba_head_dim, self.ssm_groups, self.ssm_state
        inner, k = self.mamba_inner, self.conv_kernel
        # columns of proj: gate | x | B | C | dt
        borders = (inner, 2 * inner, 2 * inner + g * n, inner + self.conv_dim)
        with hlo_scopes.scope("ssm/in_proj"):
            proj = h @ p["in_proj"].astype(dtype)
        # One chain, two realisations, chosen by what is seen here: on a TPU,
        # with borders on 128-lane blocks and whole tiles of tokens
        # (takes_conv_kernel), a kernel pair reads x, B and C in proj where
        # they lie and keeps the float32 values in VMEM
        # (``ssm.conv_sites_kernel``); anything else is the chain below,
        # differentiable by plain autodiff (``ssm.conv_sites_xla``), which
        # puts them through HBM nine times over (PERF.md, PR 38).
        if takes_conv_kernel(s, borders, k, proj.dtype):
            from tpu_dist.ops.causal_conv1d import causal_conv1d  # noqa: PLC0415

            counters_lib.inc("ssm.conv_sites_kernel")
            with hlo_scopes.scope("ssm/conv1d"):
                gate, x, b, c, dt = causal_conv1d(
                    proj, p["conv_w"], p["conv_b"], borders=borders, activation="silu",
                    interpret=False)  # only on a TPU
        else:
            counters_lib.inc("ssm.conv_sites_xla")
            with hlo_scopes.scope("ssm/in_proj"):
                gate, xbc, dt = jnp.split(proj, [inner, inner + self.conv_dim], axis=-1)
            with hlo_scopes.scope("ssm/conv1d"):
                padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
                conv = sum(padded[:, i:i + s] * p["conv_w"][i] for i in range(k)) + p["conv_b"]
                xbc = jax.nn.silu(conv).astype(dtype)
                x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
        with hlo_scopes.scope("ssm/conv1d"):
            dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        # Around the scan everything stays [B, S, inner], the layout the
        # projections and the scan kernel share: on the TPU a [.., heads, 64]
        # or [.., groups, inner/G] view of it is another tiling, and XLA
        # copies the whole array to get there and back (PERF.md, PR 34).
        with hlo_scopes.scope("ssm/scan"):
            y = ssm_scan(x.reshape(bsz, s, heads, hp), dt, -jnp.exp(p["A_log"].astype(jnp.float32)),
                         b.reshape(bsz, s, g, n), c.reshape(bsz, s, g, n), self.chunk_size)
            skip = jnp.repeat(p["D"].astype(jnp.float32), hp)    # a head's D over its channels
            y = y.reshape(bsz, s, inner) + (skip * x).astype(dtype)
        # gate before the norm; the norm is over groups of inner/G channels
        with hlo_scopes.scope("ssm/gate_norm"):
            y = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
            y = jnp.concatenate([rms_norm(1.0, part, self.eps) for part in jnp.split(y, g, axis=-1)], -1)
            y = (y * p["gnorm"]).astype(dtype)
        with hlo_scopes.scope("ssm/out_proj"):
            return y @ p["out_proj"].astype(dtype)

    def _short_conv(self, p, h, dtype):
        """LFM2's gated short convolution: ``[B | C | u] = h W_in``, ``y = C *
        conv_k(B * u)`` (depthwise, causal, no bias, no activation), ``y W_out``;
        the chain between the two products in float32."""
        s, k, f32 = h.shape[1], self.conv_kernel, jnp.float32
        with hlo_scopes.scope("conv/in_proj"):
            proj = h @ p["in_proj"].astype(dtype)
        counters_lib.inc("conv.sites")
        with hlo_scopes.scope("conv/short"):
            b, c, u = jnp.split(proj, 3, axis=-1)
            v = jnp.pad(b.astype(f32) * u.astype(f32), ((0, 0), (k - 1, 0), (0, 0)))
            w = sum(v[:, j:j + s] * p["conv_w"][j].astype(f32) for j in range(k))
            y = (c.astype(f32) * w).astype(dtype)
        with hlo_scopes.scope("conv/out_proj"):
            return y @ p["out_proj"].astype(dtype)

    def _dense_ffn(self, p, h, dtype):
        """``(silu(h W_1) * (h W_3)) W_2``, the gate's product in float32."""
        with hlo_scopes.scope("ffn/dense"):
            gate = (h @ p["w1"].astype(dtype)).astype(jnp.float32)
            up = (h @ p["w3"].astype(dtype)).astype(jnp.float32)
            return (jax.nn.silu(gate) * up).astype(dtype) @ p["w2"].astype(dtype)

    def _norm_rotate(self, scale, x):
        """A projection's heads ``x [B, S, heads, D]`` as attention takes
        them: RMSNorm over each head's ``D`` channels (``qk_norm``), then the
        rotation by position (``rope_theta``; channel ``i`` pairs with ``i +
        D/2``, positions 0..S-1), in float32, back in ``x``'s dtype."""
        xf = x.astype(jnp.float32)
        if self.qk_norm:
            xf = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + self.eps)
            xf = xf * scale.astype(jnp.float32)
        if self.rope_theta is not None:
            half = x.shape[-1] // 2
            inv = jnp.asarray(np.float32(self.rope_theta ** (-np.arange(half) / half)))
            angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv   # [S, D/2]
            cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
            sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
            x1, x2 = xf[..., :half], xf[..., half:]
            xf = xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
        return xf.astype(x.dtype)

    def _attention(self, p, h, dtype, attn_impl):
        bsz, s, _ = h.shape
        with hlo_scopes.scope("attn/qkv"):
            q = (h @ p["wq"].astype(dtype)).reshape(bsz, s, self.attn_heads, self.attn_head_dim)
            k = (h @ p["wk"].astype(dtype)).reshape(bsz, s, self.kv_heads, self.attn_head_dim)
            v = (h @ p["wv"].astype(dtype)).reshape(bsz, s, self.kv_heads, self.attn_head_dim)
        if self.qk_norm or self.rope_theta is not None:
            counters_lib.inc("rope.sites")
            with hlo_scopes.scope("attn/rope"):
                q = self._norm_rotate(p.get("q_norm"), q)
                k = self._norm_rotate(p.get("k_norm"), k)
        with hlo_scopes.scope("attn/causal"):
            o = attn_lib.attention(q, k, v, causal=True, impl=attn_impl)
        with hlo_scopes.scope("attn/out"):
            return o.reshape(bsz, s, -1) @ p["wo"].astype(dtype)

    def router_scores(self, p, h):
        """``sigmoid(h W_r)`` over all experts in float32, ``h [T, d]``."""
        logits = jnp.dot(h.astype(jnp.float32), p["router"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        return jax.nn.sigmoid(logits)

    def _experts(self, p, bias, h, dtype):
        bsz, s, d = h.shape
        x = h.reshape(bsz * s, d)
        with hlo_scopes.scope("moe/route"):
            scores = self.router_scores(p, x)
            chosen, weights = expert_lib.choose_experts(
                scores, bias, self.top_k, self.routed_scaling, self.topk_eps)
            load = jnp.zeros((self.n_experts,), jnp.float32).at[chosen.reshape(-1)].add(1.0)
        gated = {}
        if self.gated_experts:
            counters_lib.inc("moe.sites_gated")
            gated = {"w_gate": p["w_gate"].astype(dtype)}
        with hlo_scopes.scope("moe/experts"):
            routed, rows = expert_lib.dropless_experts(
                x, chosen, weights.astype(dtype), p["w_up"].astype(dtype),
                p["w_down"].astype(dtype), held=self.experts_held,
                capacity=self.buffer_rows(bsz * s),
                activation=jax.nn.silu if self.gated_experts else _relu2, **gated,
            )
        if self.shared_width:
            with hlo_scopes.scope("moe/shared"):
                shared = _relu2(x @ p["shared_up"].astype(dtype)) @ p["shared_down"].astype(dtype)
            routed = routed + shared
        return routed.reshape(bsz, s, d), load, rows

    def hidden_states(self, params, state, tokens, *, train: bool, compute_dtype,
                      attn_impl: Optional[str] = None, router_inputs: bool = False,
                      axis_name=None):
        """Final-norm hidden states ``[B, S, d]``, the new state and the
        expert layers' counts. Training recomputes each layer in the backward
        pass. ``router_inputs=True`` also returns every expert layer's
        normed input ``[T, d]`` (what its router scores; for a balancing
        loop outside the model). ``axis_name``: the mesh axes the batch is
        split over; the balancing rule then moves by the whole batch's load."""
        dtype = compute_dtype
        with hlo_scopes.scope("lm/embed"):
            x = params["embed"].astype(dtype)[tokens]
        bias = state["router_bias"]
        loads, rows, seen = [], [], []
        for depth, (kind, p) in enumerate(zip(self.pattern, params["layers"])):
            remat = train and (self.recompute is None or depth in self.recompute)
            if kind == "E":
                def f(p, x, b):
                    with hlo_scopes.scope("block/norm"):
                        y = rms_norm(p["norm"], x, self.eps)
                    return self._experts(p, b, y, dtype)

                if router_inputs:
                    seen.append(rms_norm(p["norm"], x, self.eps).reshape(-1, x.shape[-1]))
                f = jax.checkpoint(f) if remat else f
                out, load, n_rows = f(p, x, bias[len(loads)])
                loads.append(load)
                rows.append(n_rows)
            else:
                def f(p, x, kind=kind):
                    with hlo_scopes.scope("block/norm"):
                        y = rms_norm(p["norm"], x, self.eps)
                    if kind == "*":
                        return self._attention(p, y, dtype, attn_impl)
                    return {"M": self._mixer, "C": self._short_conv, "F": self._dense_ffn}[kind](
                        p, y, dtype)

                out = (jax.checkpoint(f) if remat else f)(p, x)
            with hlo_scopes.scope("block/residual"):
                x = x + out
        loads = jnp.stack(loads) if loads else jnp.zeros((0, self.n_experts), jnp.float32)
        stats = expert_lib.load_stats(loads, rows, self.experts_held)
        new_state = state
        if train and self.n_expert_layers:
            if axis_name is not None:
                loads = lax.psum(loads, axis_name)  # the whole batch's load
            # the auxiliary-loss-free rule: raise the bias of an expert below
            # the mean load, lower it above; selection only, no gradient
            mean = loads.mean(axis=-1, keepdims=True)
            new_state = {"router_bias": bias + self.bias_rate * jnp.sign(mean - loads)}
        with hlo_scopes.scope("lm/final_norm"):
            h = rms_norm(params["norm_f"], x, self.eps)
        out = (h, new_state, stats)
        return out + (seen,) if router_inputs else out

    # -- the model's two entries ------------------------------------------------

    def head_matrix(self, params, dtype):
        """``[d, vocab]`` in ``dtype``: the head, or (``tied_head``) the
        embedding read transposed, whose gradient then sums both uses."""
        return (params["embed"].T if self.tied_head else params["head"]).astype(dtype)

    def apply(self, params, state, tokens, train: bool = False, axis_name=None,
              compute_dtype=jnp.float32, attn_impl: Optional[str] = None):
        """``[B, S]`` token ids to ``[B, S, vocab]`` float32 logits, all kept:
        for small shapes and tests; training goes through :meth:`loss`."""
        h, new_state, _ = self.hidden_states(
            params, state, tokens, train=train, compute_dtype=compute_dtype, attn_impl=attn_impl)
        logits = jnp.einsum("bsd,dv->bsv", h, self.head_matrix(params, compute_dtype),
                            preferred_element_type=jnp.float32)
        return logits, new_state

    def loss(self, params, state, tokens, targets, *, train: bool, compute_dtype=jnp.float32,
             sample_weight=None, attn_impl: Optional[str] = None, axis_name=None):
        """Mean over all positions of the float32 next-token cross-entropy.
        Returns ``(loss, new_state, stats)``; ``stats`` has the sums the steps'
        metrics are made of (``nll_sum``, ``weight_sum``, ``top1``, ``top5``)
        and the expert layers' counts. ``sample_weight [B]`` weighs whole
        sequences (eval's padding mask)."""
        h, new_state, stats = self.hidden_states(
            params, state, tokens, train=train, compute_dtype=compute_dtype,
            attn_impl=attn_impl, axis_name=axis_name)
        b, s, d = h.shape
        w = jnp.ones((b,), jnp.float32) if sample_weight is None else sample_weight
        with hlo_scopes.scope("lm/head_loss"):
            nll, top1, top5 = F.blocked_cross_entropy(
                h.reshape(b * s, d), self.head_matrix(params, compute_dtype),
                targets.reshape(b * s), jnp.repeat(w.astype(jnp.float32), s),
                block=self.head_block,
            )
        weight_sum = w.sum() * s
        stats = dict(stats, nll_sum=nll, weight_sum=weight_sum, top1=top1, top5=top5,
                     tokens=jnp.float32(b * s))
        return nll / jnp.maximum(weight_sum, 1.0), new_state, stats


    def count_stats(self, m: dict) -> str:
        """One fetched step's metrics (what :meth:`loss` returned beside the
        loss, reduced over replicas by the step) into the process's counters,
        and the words a step line shows of them. The trainer calls it where
        it fetches a step's metrics anyway, so no step gains a fetch: sums
        become counters over the fetched steps, the load ratio a gauge, its
        peak and its running sum (``docs/observability.md``)."""
        counters_lib.inc("lm.tokens", m["tokens"])
        if "moe_rows_live" not in m:
            return ""
        for key in ("rows_live", "rows_balanced", "rows_over_cap"):
            counters_lib.inc("moe." + key, m["moe_" + key])
        ratio = m["moe_load_max_over_mean"]
        counters_lib.inc("moe.steps_observed")
        counters_lib.inc("moe.load_max_over_mean_sum", ratio)
        counters_lib.set_gauge("moe.load_max_over_mean", ratio)
        counters_lib.set_gauge("moe.load_max_over_mean_peak", max(
            ratio, counters_lib.snapshot().get("moe.load_max_over_mean_peak", 0.0)))
        return (f" moe_load={ratio:.3f} "
                f"rows={m['moe_rows_live'] / max(m['moe_rows_balanced'], 1.0):.3f}")


_jitted_init = jax.jit(HybridDecoderDef._init, static_argnums=(0, 2))


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def nemotron3_nano_share(num_classes: int = 0) -> HybridDecoderDef:
    """One chip's share of NVIDIA-Nemotron-3-Nano-30B-A3B at its published
    widths: the first seven layers (one whole ``MEMEM*E`` unit of the
    52-layer pattern), experts 0-7 of 128 and 16,384 of 131,072 vocabulary
    rows, as one of 16 expert-parallel chips would hold them; 528.1M
    parameters. ``num_classes`` is the zoo's calling convention and unused:
    the vocabulary is the model's."""
    return HybridDecoderDef(
        pattern="MEMEM*E", vocab_size=16384, seq_len=8192, hidden=2688,
        mamba_heads=64, mamba_head_dim=64, ssm_groups=8, ssm_state=128, conv_kernel=4,
        chunk_size=128, attn_heads=32, kv_heads=2, attn_head_dim=128,
        n_experts=128, experts_held=(0, 8), top_k=6, expert_width=1856, shared_width=3712,
        rescale_layers=52, recompute=(0, 2),
    )


def nemotron_h_tiny(num_classes: int = 0) -> HybridDecoderDef:
    """Every kind of layer at toy widths, for the CPU: 4 experts of 16 held."""
    return HybridDecoderDef(
        pattern="ME*E", vocab_size=64, seq_len=32, hidden=32,
        mamba_heads=4, mamba_head_dim=8, ssm_groups=2, ssm_state=8, conv_kernel=4,
        chunk_size=8, attn_heads=4, kv_heads=2, attn_head_dim=8,
        n_experts=16, experts_held=(0, 4), top_k=2, expert_width=16, shared_width=32,
        head_block=16,
    )


def lfm2_24b_a2b_share(num_classes: int = 0) -> HybridDecoderDef:
    """One chip's share of LiquidAI's LFM2-24B-A2B at its published widths:
    layers 1-5 of 40 (the second dense layer, then one whole period of four
    expert layers: ``conv | attention, conv, conv, conv``), a layer two blocks
    (operator, feed-forward): ``CF *E CE CE CE``; experts 0-7 of 64 and 8,192
    of 65,536 vocabulary rows, as one of 8 expert-parallel chips would hold
    them; 469.3M parameters. The four `C` blocks and the first `E` block are
    recomputed in the backward pass (``benchmarks/configs/lfm2_24b_a2b.json``,
    ``remat``)."""
    return HybridDecoderDef(
        pattern="CF*ECECECE", vocab_size=8192, seq_len=8192, hidden=2048,
        mamba_heads=0, mamba_head_dim=0, ssm_groups=0, ssm_state=0, conv_kernel=3, chunk_size=0,
        attn_heads=32, kv_heads=8, attn_head_dim=64,
        n_experts=64, experts_held=(0, 8), top_k=4, expert_width=1536, shared_width=0,
        routed_scaling=1.0, dense_width=11776, gated_experts=True, qk_norm=True,
        rope_theta=1e6, tied_head=True, topk_eps=1e-6, rescale_layers=40,
        recompute=(0, 3, 4, 6, 8),
    )


def lfm2_moe_tiny(num_classes: int = 0) -> HybridDecoderDef:
    """Every kind of LFM2 block at toy widths, for the CPU: 4 experts of 16
    held, and a buffer for every pair (a toy router at a toy learning rate may
    send them all here)."""
    return HybridDecoderDef(
        pattern="CF*ECE", vocab_size=64, seq_len=32, hidden=32,
        mamba_heads=0, mamba_head_dim=0, ssm_groups=0, ssm_state=0, conv_kernel=3, chunk_size=0,
        attn_heads=4, kv_heads=2, attn_head_dim=8,
        n_experts=16, experts_held=(0, 4), top_k=2, expert_width=16, shared_width=0,
        routed_scaling=1.0, dense_width=48, gated_experts=True, qk_norm=True,
        rope_theta=1e6, tied_head=True, topk_eps=1e-6, head_block=16, capacity_factor=4.0,
    )
