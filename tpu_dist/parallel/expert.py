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

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from tpu_dist.obs import counters as counters_lib


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


# -- dropless routing over a held share of the experts ------------------------
#
# The capacity layer above drops what overflows a slot. The functions below
# drop nothing, and serve a layer that is told which of the routed experts it
# holds (``held = (first, count)`` of a wider deployment's experts): the
# router scores every expert, and the chosen (token, expert) pairs whose
# expert is held here are sorted by expert into one row buffer of static
# size and multiplied tile by tile, every tile one expert's: on a TPU, at
# shapes its blocks fit, by the Pallas kernel pair of ops/grouped_matmul.py,
# elsewhere by a loop of XLA dots (``grouped_matmul``). What the absent
# experts would add is left out, and no code stands in for them. An expert is
# ``activation(v W_up) W_down`` or, with a gate matrix, the gated form
# ``(activation(v W_gate) * (v W_up)) W_down`` (SwiGLU with ``silu``): two
# grouped products into the same sorted rows, a third out.


def choose_experts(scores, bias, top_k: int, scaling: float, eps: float = 1e-20):
    """The ``top_k`` experts of each token by ``scores + bias`` (the bias
    moves the selection only), weighted by their scores renormalised over the
    chosen ones (``eps`` added to their sum: a model publishes its own) times
    ``scaling``. ``scores [T, E]`` float32 in (0, 1).
    Returns ``(chosen [T, k] int32, weights [T, k] float32)``."""
    _, chosen = lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, scaling * picked / (picked.sum(-1, keepdims=True) + eps)


GROUP_TILE = 512  # rows of one grouped-product tile: every tile belongs to one expert


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def takes_gmm_kernel(rows: int, a: int, b: int, dtype) -> bool:
    """Where :func:`grouped_matmul` takes the Pallas kernel pair of
    ``ops/grouped_matmul.py``: a TPU, and tiles of ``rows`` rows against
    ``[a, b]`` matrices that its blocks fit (whole 128-row tiles; widths in
    128-lane blocks, or whole)."""
    if not _on_tpu():
        return False
    from tpu_dist.ops.grouped_matmul import fits  # noqa: PLC0415

    return fits(rows, a, b, dtype)


def _tile_dot(x, w, transpose: bool):
    """``x [rows, a]`` times one expert's ``w`` (``[a, b]``, or ``[b, a]`` read
    transposed), accumulated in float32, back in ``x``'s dtype."""
    dims = (((1,), (1 if transpose else 0,)), ((), ()))
    return lax.dot_general(x, w, dims, preferred_element_type=jnp.float32).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_expert, n_live, transpose=False):
    """``x [tiles, rows, a]`` times, tile by tile, the expert matrix
    ``w[tile_expert[t]]`` of ``w [experts, a, b]`` (``[experts, b, a]`` with
    ``transpose``), the expert's matrix read in place. Tiles from ``n_live``
    on are skipped and give zeros, whatever their rows hold. The gradients:
    ``dx`` is the same product against the transposed matrices, ``dw`` is
    summed in float32 over all of an expert's tiles and cast once.

    One contract, two realisations, chosen by what is seen here
    (:func:`takes_gmm_kernel`): on a TPU, at shapes its blocks fit, the
    kernel pair of ``ops/grouped_matmul.py``, told each tile's expert by
    scalar prefetch: a tile's output written once, an expert's weight
    gradient summed in VMEM and written once (``moe.sites_gmm_kernel``
    counts the products traced, the backward's ``dx`` one of them).
    Anything else (``moe.sites_gmm_xla``) is one loop over the tiles, an XLA
    dot a tile, ``dw`` summed into its expert's slab tile by tile."""
    a, b = (w.shape[2], w.shape[1]) if transpose else w.shape[1:]
    if takes_gmm_kernel(x.shape[1], a, b, x.dtype):
        from tpu_dist.ops.grouped_matmul import gmm  # noqa: PLC0415

        counters_lib.inc("moe.sites_gmm_kernel")
        return gmm(x, w, tile_expert, n_live, transpose, interpret=not _on_tpu())
    counters_lib.inc("moe.sites_gmm_xla")

    def tile(args):
        t, x_t, e = args
        return lax.cond(
            t < n_live,
            lambda: _tile_dot(x_t, lax.dynamic_index_in_dim(w, e, keepdims=False), transpose),
            lambda: jnp.zeros((x.shape[1], w.shape[1 if transpose else 2]), x.dtype),
        )

    return lax.map(tile, (jnp.arange(x.shape[0]), x, tile_expert))


def _grouped_matmul_fwd(x, w, tile_expert, n_live, transpose):
    return grouped_matmul(x, w, tile_expert, n_live, transpose), (x, w, tile_expert, n_live)


def _grouped_matmul_bwd(transpose, res, dy):
    x, w, tile_expert, n_live = res
    dx = grouped_matmul(dy, w, tile_expert, n_live, not transpose)
    if takes_gmm_kernel(x.shape[1], x.shape[2], dy.shape[2], x.dtype):
        from tpu_dist.ops.grouped_matmul import tgmm  # noqa: PLC0415

        dw = tgmm(x, dy, tile_expert, n_live, w.shape[0], w.dtype, transpose, interpret=not _on_tpu())
        return dx, dw, None, None

    def tile(dw, args):
        t, x_t, dy_t, e = args
        a, b = (dy_t, x_t) if transpose else (x_t, dy_t)
        part = lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return lax.cond(t < n_live, lambda: dw.at[e].add(part), lambda: dw), None

    dw, _ = lax.scan(tile, jnp.zeros(w.shape, jnp.float32),
                     (jnp.arange(x.shape[0]), x, dy, tile_expert))
    return dx, dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# -- the rows back in token order ------------------------------------------------
#
# The dispatch gathers ``x[token]`` into the sorted buffer and the combine sums
# the buffer's rows back into token order. A gather is the cheap direction;
# the sum, which is also the gather's transpose in the backward, is the
# scatter ``zeros(f32[T, d]).at[token].add(rows)``. Where the kernel of
# ops/expert_combine.py fits, both sums are that kernel instead, told by
# ``runs`` where each block of tokens' rows lie.


def takes_combine_kernel(tokens: int, width: int, rows: int, dtype) -> bool:
    """Where the combine and the dispatch's backward take the kernel of
    ``ops/expert_combine.py``: a TPU, whole blocks of tokens, a width in
    128-lane blocks, whole chunks of buffer rows, VMEM within budget."""
    if not _on_tpu():
        return False
    from tpu_dist.ops.expert_combine import fits  # noqa: PLC0415

    return fits(tokens, width, rows, dtype)


def _buffer(chosen, held, capacity: int) -> dict:
    """The sorted row buffer of :func:`dropless_experts`: ``tile`` rows a
    tile, ``n_tiles`` tiles, each tile's expert, ``n_live`` live tiles, and
    for each of the ``n_tiles * tile`` buffer rows its ``pair`` (token-major
    pair id), ``token`` and ``valid`` (``[rows, 1]``: a live row of its
    expert); ``live`` rows were routed to a held expert, ``over`` of them
    past ``capacity``. ``key``, ``order`` and ``first_row`` are what
    :func:`_block_runs` reads."""
    t, k = chosen.shape
    first, count = held
    tile = min(GROUP_TILE, -(-capacity // 8) * 8)       # a small buffer is one short tile an expert
    n_tiles = -(-capacity // tile) + count
    local = chosen.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)  # absent experts sort last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    live = sizes.sum()
    ends = jnp.minimum(jnp.cumsum(sizes), capacity)
    sizes = jnp.diff(ends, prepend=0)                       # what the buffer takes
    starts = ends - sizes
    tiles = -(-sizes // tile)                               # whole tiles an expert
    tile_ends = jnp.cumsum(tiles)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_ends, jnp.arange(n_tiles), side="right"), count - 1)
    # buffer row -> its place in the sorted pairs, or past the expert's rows
    row = jnp.arange(n_tiles * tile)
    e = jnp.repeat(tile_expert, tile)
    within = row - (tile_ends - tiles)[e] * tile
    pair = order[jnp.clip(starts[e] + within, 0, t * k - 1)]
    return {
        "tile": tile, "n_tiles": n_tiles, "tile_expert": tile_expert, "n_live": tile_ends[-1],
        "pair": pair, "token": pair // k,
        "valid": ((within < sizes[e]) & (row < tile_ends[-1] * tile))[:, None],
        "live": live, "over": jnp.maximum(live - capacity, 0),
        "key": key, "order": order, "first_row": (tile_ends - tiles) * tile - starts,
        "k": k, "count": count, "capacity": capacity,
    }


def _block_runs(buf, block: int):
    """``[t / block, count, 2]``: for each block of ``block`` tokens and each
    held expert, the first and last-plus-one buffer row of the expert's rows
    whose tokens lie in the block. The sorted pairs' ``key * t + token``
    rises (a stable sort of token-major pairs by expert), so a block's rows of
    one expert are one run: its ends are where the block's first token and
    the next block's fall in that order, clipped to the buffer's capacity and
    moved to the expert's first buffer row."""
    key, order, k, count = buf["key"], buf["order"], buf["k"], buf["count"]
    t = key.shape[0] // k
    sorted_ids = key[order] * t + order // k
    edges = jnp.arange(count)[:, None] * t + jnp.arange(0, t + 1, block)[None, :]
    pos = jnp.searchsorted(sorted_ids, edges.reshape(-1)).reshape(count, -1)
    rows = jnp.minimum(pos, buf["capacity"]) + buf["first_row"][:, None]
    return jnp.stack([rows[:, :-1], rows[:, 1:]], -1).swapaxes(0, 1).astype(jnp.int32)


def _sum_rows(src, token, scale, runs, over, n_tokens, out_dtype):
    from tpu_dist.ops.expert_combine import tokens_from_runs  # noqa: PLC0415

    counters_lib.inc("moe.sites_combine_kernel")
    return tokens_from_runs(src, token, scale, runs, over, n_tokens, out_dtype,
                            interpret=not _on_tpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(n_tokens, x, token, valid, runs):
    """``where(valid, x[token], 0)``; its backward sums the rows' cotangents
    into their tokens through the kernel, in float32, cast once."""
    return jnp.where(valid, x[token], 0)


def _dispatch_fwd(n_tokens, x, token, valid, runs):
    return _dispatch(n_tokens, x, token, valid, runs), (token, runs)


def _dispatch_bwd(n_tokens, res, d_rows):
    token, runs = res
    return _sum_rows(d_rows, token, None, runs, 0, n_tokens, d_rows.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(n_tokens, y, weight_row, token, valid, runs, over):
    """``zeros(f32[T, d]).at[token].add(where(valid, f32(y) * weight_row, 0))``,
    NaN where ``over > 0``, in ``y``'s dtype: the kernel. The backward reads
    the output's cotangent at the rows' tokens (gathers): ``d y = weight_row *
    d_out[token]`` and ``d weight_row = sum_c f32(y) * f32(d_out[token])``."""
    return _sum_rows(y, token, weight_row, runs, over, n_tokens, y.dtype)


def _combine_fwd(n_tokens, y, weight_row, token, valid, runs, over):
    return _combine(n_tokens, y, weight_row, token, valid, runs, over), (y, weight_row, token, valid, over)


def _combine_bwd(n_tokens, res, d_out):
    y, weight_row, token, valid, over = res
    live = valid & (over == 0)
    g = d_out[token].astype(jnp.float32)
    d_y = jnp.where(live, weight_row.astype(jnp.float32)[:, None] * g, 0).astype(y.dtype)
    d_w = jnp.where(live[:, 0], jnp.sum(y.astype(jnp.float32) * g, axis=-1), 0)
    return d_y, d_w.astype(weight_row.dtype), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def dropless_experts(x, chosen, weights, w_up, w_down, *, held, capacity: int, activation,
                     w_gate=None):
    """``sum_k weights[t, k] * expert_{chosen[t, k]}(x[t])`` over the chosen
    experts that are held, ``expert_e(v) = activation(v w_up[e]) w_down[e]``
    or, given ``w_gate``, ``(activation(v w_gate[e]) * (v w_up[e])) w_down[e]``.

    ``x [T, d]``; ``w_up`` (and ``w_gate``) ``[count, d, f]``, ``w_down
    [count, f, d]`` for the
    experts ``first .. first + count - 1``. The pairs whose expert is held
    are sorted by expert into a row buffer, each expert's rows padded to
    whole tiles of ``GROUP_TILE`` rows, and multiplied tile by tile
    (:func:`grouped_matmul`); the buffer takes ``capacity`` live rows
    (``capacity / GROUP_TILE + count`` tiles), however they fall on the experts.
    The rows come back to token order weighted and summed in float32: where
    :func:`takes_combine_kernel` says so, by the kernel of
    ``ops/expert_combine.py`` (``moe.sites_combine_kernel``: the combine, and
    the dispatch gather's backward), elsewhere by XLA's scatter-add
    (``moe.sites_combine_xla``, two a layer traced).
    A step that would need more is never cut short in silence: its output is
    NaN (the trainer's guard stops on the loss) and ``rows_over_cap`` counts
    the rows. Returns ``(out [T, d], {"rows_live", "rows_over_cap"})``."""
    t = chosen.shape[0]
    buf = _buffer(chosen, held, capacity)
    shape = (buf["n_tiles"], buf["tile"], -1)
    token, valid, tile_expert, n_live = buf["token"], buf["valid"], buf["tile_expert"], buf["n_live"]
    kernel = takes_combine_kernel(t, x.shape[1], token.shape[0], x.dtype)
    if kernel:
        from tpu_dist.ops.expert_combine import TOKEN_BLOCK  # noqa: PLC0415

        runs = _block_runs(buf, TOKEN_BLOCK)
        rows = _dispatch(t, x, token, valid, runs).reshape(shape)
    else:
        counters_lib.inc("moe.sites_combine_xla", 2)  # the combine, and the dispatch's transpose
        rows = jnp.where(valid, x[token], 0).reshape(shape)
    h = grouped_matmul(rows, w_up, tile_expert, n_live)
    if w_gate is None:
        h = activation(h)
    else:
        gate = grouped_matmul(rows, w_gate, tile_expert, n_live)
        h = (activation(gate.astype(jnp.float32)) * h.astype(jnp.float32)).astype(x.dtype)
    y = grouped_matmul(h, w_down, tile_expert, n_live).reshape(token.shape[0], -1)
    weight_row = weights.reshape(-1)[buf["pair"]]
    over = buf["over"]
    if kernel:
        out = _combine(t, y, weight_row, token, valid, runs, over)
    else:
        y = jnp.where(valid, y.astype(jnp.float32) * weight_row[:, None], 0)
        out = jnp.zeros((t, x.shape[1]), jnp.float32).at[token].add(y)
        out = jnp.where(over > 0, jnp.nan, out).astype(x.dtype)
    return out, {"rows_live": buf["live"], "rows_over_cap": over}


def load_stats(loads, rows, held):
    """What a step's expert layers did, as float32 scalars: ``loads [L, E]``
    (tokens that chose each expert, a layer) and the layers' ``rows`` dicts.
    ``moe_rows_balanced`` is what a perfectly balanced router would have sent
    to the held experts. The counts are sums (over replicas too); what is a
    worst case instead sits under ``"maxima"``, which is how a step knows to
    take its maximum over replicas: ``moe_load_max_over_mean``, the worst
    layer's busiest expert over the mean."""
    if not rows:
        return {}
    total = loads.sum(axis=-1)
    return {
        "moe_rows_live": sum(r["rows_live"] for r in rows).astype(jnp.float32),
        "moe_rows_over_cap": sum(r["rows_over_cap"] for r in rows).astype(jnp.float32),
        "moe_rows_balanced": (total * held[1] / loads.shape[-1]).sum(),
        "maxima": {
            "moe_load_max_over_mean":
                (loads.max(axis=-1) / jnp.maximum(loads.mean(axis=-1), 1.0)).max(),
        },
    }
