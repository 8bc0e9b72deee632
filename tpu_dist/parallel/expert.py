"""Expert parallelism: top-k Mixture-of-Experts over a mesh axis, with
capacity-based dispatch/combine through ``lax.all_to_all``.

Beyond the reference's scope (SURVEY §2.3: no EP anywhere), built so the
``expert`` mesh axis is exercised for real:

* every device holds ``E/n`` experts' weights (expert-sharded params),
* tokens are routed top-k (k=1 is Switch, k=2 is GShard-style) with a
  capacity limit ``C`` per expert; first choices of every token claim
  slots before any second choice does (choice-major priority, the GShard
  rule),
* dispatch: one-hot einsum packs tokens into ``[E, C, d]`` slots, then ONE
  ``all_to_all`` over the axis moves each expert's slots to its owner,
* experts run their FFN on their ``[n_local_tokens... , C, d]`` slab,
* combine: the reverse ``all_to_all`` + gate-weighted einsum restores
  token order (for k>1 the k gates are renormalized to sum to one).

Tokens that overflow an expert's capacity are dropped (standard Switch
behavior) — that choice contributes 0 and the residual connection carries
the token.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax


@dataclass(frozen=True)
class MoE:
    """Top-k MoE FFN. ``n_experts`` must be a multiple of the axis size.

    ``init(key, d_model, d_ff)`` → params with leading expert dim E.
    Shard params over the axis with ``P('expert')`` on that dim (or slice
    manually per device inside shard_map via ``params_local``).

    ``top_k=1`` gates by the raw softmax probability (Switch); ``top_k>1``
    renormalizes the chosen probabilities to sum to one (GShard).
    """

    n_experts: int
    capacity_factor: float = 1.25
    top_k: int = 1

    def init(self, key, d_model: int, d_ff: int):
        k1, k2, k3 = jax.random.split(key, 3)
        E = self.n_experts
        s1 = d_model ** -0.5
        s2 = d_ff ** -0.5
        return {
            "router": jax.random.normal(k1, (d_model, E)) * s1,
            "w_in": jax.random.normal(k2, (E, d_model, d_ff)) * s1,
            "w_out": jax.random.normal(k3, (E, d_ff, d_model)) * s2,
        }

    # -- dense reference (single device, no sharding) -----------------------

    def apply_dense(self, params, x, *, with_aux: bool = False):
        """[T, d] → [T, d]; ground truth for the EP path. ``with_aux=True``
        also returns the load-balancing loss (see :meth:`aux_loss`)."""
        T, d = x.shape
        C = self._capacity(T)
        pack, combine, aux = self._route(params, x, C)
        slots = jnp.einsum("tec,td->ecd", pack, x)            # [E, C, d]
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", slots, params["w_in"]))
        out = jnp.einsum("ecf,efd->ecd", h, params["w_out"])  # [E, C, d]
        y = jnp.einsum("tec,ecd->td", combine, out)
        return (y, aux) if with_aux else y

    # -- expert-parallel (inside shard_map over `axis`) ---------------------

    def apply_ep(self, params_repl_router, w_in_local, w_out_local, x, axis: str,
                 *, with_aux: bool = False):
        """Expert-parallel forward for THIS device's token shard ``x``
        [T_loc, d]. ``w_in_local``/``w_out_local``: [E/n, d, f] local expert
        slabs; router weights replicated.

        Every device dispatches its tokens into per-expert capacity slots,
        one ``all_to_all`` exchanges slots so each device receives all
        devices' slots for ITS experts, the local experts run, and the
        reverse ``all_to_all`` + combine restores token order.
        """
        n = lax.axis_size(axis)
        T_loc, d = x.shape
        E = self.n_experts
        e_loc = E // n
        C = self._capacity(T_loc)

        pack, combine, aux = self._route({"router": params_repl_router}, x, C)
        slots = jnp.einsum("tec,td->ecd", pack, x)             # [E, C, d]
        # group by owner device: [n, e_loc, C, d] → all_to_all over axis
        slots = slots.reshape(n, e_loc, C, d)
        recv = lax.all_to_all(slots, axis, split_axis=0, concat_axis=0, tiled=False)
        # recv: [n, e_loc, C, d] — slot blocks from every peer for MY experts
        h = jax.nn.gelu(jnp.einsum("necd,edf->necf", recv, w_in_local))
        out = jnp.einsum("necf,efd->necd", h, w_out_local)
        # send results back to the token owners
        back = lax.all_to_all(out, axis, split_axis=0, concat_axis=0, tiled=False)
        back = back.reshape(E, C, d)
        y = jnp.einsum("tec,ecd->td", combine, back)
        return (y, aux) if with_aux else y

    # -- shared routing ------------------------------------------------------

    def _capacity(self, T: int) -> int:
        return max(1, int(self.capacity_factor * self.top_k * T / self.n_experts))

    def _route(self, params, x, C: int):
        """Top-k routing with capacity. Returns two [T, E, C] dispatch
        tensors — ``pack`` (binary: which slot each token occupies, up to k
        of them) and ``combine`` (gate-weighted: how expert outputs sum
        back per token) — plus the scalar load-balancing auxiliary loss
        (Switch Transformer §2.2): ``E · Σ_e f_e · P_e`` with ``f_e`` the
        fraction of tokens whose TOP choice is expert e (non-differentiable
        count) and ``P_e`` the mean router probability for e
        (differentiable). Minimized (→ 1) by a uniform router; the
        coefficient is the caller's (``--moe_aux_coef``)."""
        T = x.shape[0]
        E, k = self.n_experts, self.top_k
        logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_probs, topk_idx = lax.top_k(probs, k)            # [T, k]
        if k == 1:
            gates = topk_probs                                # Switch: raw prob
        else:
            gates = topk_probs / jnp.maximum(
                topk_probs.sum(-1, keepdims=True), 1e-9
            )                                                 # GShard: renorm

        # CHOICE-MAJOR slot assignment: every token's 1st choice outranks
        # any token's 2nd choice for the capacity budget
        oh = jax.nn.one_hot(topk_idx, E, dtype=jnp.int32)     # [T, k, E]
        oh_cm = oh.transpose(1, 0, 2).reshape(k * T, E)       # [k*T, E]
        pos = jnp.cumsum(oh_cm, axis=0) * oh_cm - 1           # slot per entry
        keep = (pos < C) & (pos >= 0)
        slot = jnp.where(keep, pos, -1).max(-1)               # [k*T]; -1 = drop
        pos_oh = jax.nn.one_hot(slot, C, dtype=x.dtype)       # [k*T, C]
        disp_cm = oh_cm.astype(x.dtype)[:, :, None] * pos_oh[:, None, :]
        disp_k = disp_cm.reshape(k, T, E, C)                  # per-choice

        pack = disp_k.sum(0)                                  # binary [T, E, C]
        combine = jnp.einsum("ktec,tk->tec", disp_k, gates.astype(x.dtype))

        f_e = oh[:, 0, :].astype(jnp.float32).mean(0)         # top-choice freq
        P_e = probs.mean(0)                                   # mean router prob
        aux = E * jnp.sum(f_e * P_e)
        return pack, combine, aux.astype(x.dtype)
