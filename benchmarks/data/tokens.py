"""Data kind ``tokens``: next-token prediction, one sequence a sample.

The four hooks a data kind gives the harness (``benchmarks/data/images.py``
says what each is). The data block gives the sizes: ``seq_len``,
``vocab_size``, ``epoch_steps`` (or ``n_train``) and, for a model with
routed experts, ``balance``.

A sample is one document of exactly ``seq_len`` tokens: uniform random ids
from the vocabulary (slice) the configuration holds, its targets the next
id at every position. Random ids cost the input path and the chip what real
ones do, and give every seed the same lengths.

**Every seed the same work.** A router at its random initialisation sends
its own experts anything from half to twice the balanced share of rows,
differently for every seed (``PERF.md``, PR 33; the fault the driver found
in PR 29), while a trained router is balanced: the model publishes the
mechanism, a selection bias moved by the sign of each expert's load error.
A user's checkpoint arrives balanced; the seeded one here is brought there
in ``attach``, before the first update: on the first ``BALANCE_BATCHES``
batches together (one batch alone balances to that batch: 1.23-1.31 on the
next ones, ``PERF.md``), an expert layer at a time in depth order, the same
sign rule is iterated on that layer's router scores with a shrinking step
until the largest expert's load is within ``balance.max_over_mean`` of the
mean. The scores come from the program
(``HybridDecoderDef.hidden_states(router_inputs=True)``, ``router_scores``);
only the top-k is recomputed an iteration. The rule keeps running in the
training step afterwards.

The balanced bias is model state, not a parameter, and the harness hands a
reference ``arch``, ``params``, ``inputs`` and ``targets`` and nothing else
(``harness/reference.py``), with ``params`` read leaf by leaf as what the
optimizer moved. So ``attach`` leaves the bias in the one object it shares
with the reference, the cell's ``arch`` (``router_bias``). The reference
takes an array-valued entry of ``arch`` as an argument of its program, not as
a constant (``reference.reference_program``), so its program is the same for every
seed and compiles once a configuration.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, Tuple

import numpy as np

from benchmarks.harness.manifest import Cell

BALANCE_BATCHES = 4  # the batches of epoch 0 a layer's scores are taken on
FIRST_STEP, SHRINK, MAX_ITERATIONS = 0.02, 0.97, 400  # the sign rule's step, an iteration


def make_dataset(n: int, seq_len: int, vocab_size: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(inputs int32 [n, seq_len], targets int32 [n, seq_len])``."""
    rng = np.random.default_rng([int(seed), 0x70CE])
    ids = rng.integers(0, vocab_size, size=(n, seq_len + 1), dtype=np.int32)
    return np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])


def make(cell: Cell, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """The cell's host arrays from ``--seed``."""
    d = cell.config["data"]
    return make_dataset(cell.n_train, int(d["seq_len"]), int(d["vocab_size"]), seed)


def train_config(cell: Cell) -> Dict[str, Any]:
    """The ``TrainConfig`` fields this kind sets for every cell: the
    Trainer's own token set only has to exist (``attach`` replaces it)."""
    return {"dataset": "synthetic_tokens", "synthetic_n": max(cell.global_batch, 8)}


def balance_bias(scores, top_k: int, goal: float):
    """Selection bias ``[experts]`` under which the ``top_k`` of
    ``scores + bias`` load the experts within ``goal`` (max over mean), by
    the model's own rule ``bias += step * sign(mean load - load)`` with a
    step that starts at ``FIRST_STEP`` and shrinks by ``SHRINK`` an
    iteration. Returns ``(bias, max-over-mean reached, iterations)``; one
    jitted loop on the device."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    experts = scores.shape[-1]

    def ratio_of(bias):
        _, chosen = jax.lax.top_k(scores + bias, top_k)
        load = jnp.zeros((experts,), jnp.float32).at[chosen.reshape(-1)].add(1.0)
        return load, load.max() / load.mean()

    def cond(carry):
        _, ratio, it = carry
        return (ratio > goal) & (it < MAX_ITERATIONS)

    def body(carry):
        bias, _, it = carry
        load, _ = ratio_of(bias)
        bias = bias + FIRST_STEP * SHRINK ** it * jnp.sign(load.mean() - load)
        return bias, ratio_of(bias)[1], it + 1

    zero = jnp.zeros((experts,), jnp.float32)
    return jax.lax.while_loop(cond, body, (zero, ratio_of(zero)[1], jnp.int32(0)))


def balance_router(trainer, batches, goal: float):
    """The model state with every expert layer's selection bias balanced on
    the token ``batches`` together, depth first: a layer's scores are taken
    with the layers before it already balanced. One forward program, run
    once a layer and batch."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    model, cfg = trainer.model, trainer.cfg
    dtype = jnp.bfloat16 if cfg.bf16 else jnp.float32
    params = trainer.state.params
    n_layers = model.n_expert_layers

    @jax.jit
    def layer_scores(params, state, tokens, layer):
        *_, seen = model.hidden_states(params, state, tokens, train=False,
                                       compute_dtype=dtype, router_inputs=True)
        experts = [p for kind, p in zip(model.pattern, params["layers"]) if kind == "E"]
        return jax.lax.switch(layer, [
            lambda i=i: model.router_scores(experts[i], seen[i]) for i in range(n_layers)
        ])

    solve = jax.jit(lambda s: balance_bias(s, model.top_k, goal))
    state, report = trainer.state.bn_state, []
    for i in range(n_layers):
        scores = jnp.concatenate([layer_scores(params, state, tokens, i) for tokens in batches])
        bias, ratio, its = solve(scores)
        state = {**state, "router_bias": state["router_bias"].at[i].set(bias)}
        report.append((float(ratio), int(its)))
    return state, report


def attach(trainer, cell: Cell, seed: int, inputs: np.ndarray, targets: np.ndarray) -> None:
    """Hand the arrays to the program: its own ``DistributedSampler`` and
    ``DataLoader`` over them, as ``Trainer`` builds them for integer ids
    (gather by index, no transform); then bring the router to balance on
    epoch 0's first batches and leave the balanced bias where the reference
    finds it (the module's docstring says why there)."""
    import jax  # noqa: PLC0415

    from tpu_dist.comm import mesh as mesh_lib  # noqa: PLC0415
    from tpu_dist.data.loader import DataLoader  # noqa: PLC0415
    from tpu_dist.data.sampler import DistributedSampler  # noqa: PLC0415

    tr, cfg = trainer, trainer.cfg
    nproc, pid = mesh_lib.process_count(), mesh_lib.process_index()
    tr.train_data = (inputs, targets)
    tr.train_sampler = DistributedSampler(
        len(inputs), nproc, pid, shuffle=True, seed=seed,
        drop_last=cfg.drop_last or cfg.grad_accu_steps > 1,
    )
    tr.train_loader = DataLoader(
        inputs, targets, tr.local_batch, tr.train_sampler, tr.mesh,
        seed=seed, prefetch=cfg.num_workers,
        batch_divisor=max(1, tr.n_data // nproc), shard_axes=mesh_lib.DATA_AXIS,
    )
    spec = cell.config["data"].get("balance")
    if not spec or not getattr(tr.model, "n_expert_layers", 0):
        return
    tr.train_sampler.set_epoch(0)
    it = tr.train_loader.iter_from(0)
    try:
        batches = [tokens for tokens, _ in itertools.islice(it, BALANCE_BATCHES)]
    finally:
        it.close()
    state, report = balance_router(tr, batches, float(spec["max_over_mean"]))
    tr.state = tr.state._replace(bn_state=jax.device_put(state, mesh_lib.replicated(tr.mesh)))
    cell.config["arch"]["router_bias"] = np.asarray(jax.device_get(state["router_bias"]))
    print(f"[bench] router balanced on the first {len(batches)} batch(es), "
          "(max/mean, iterations) a layer: "
          + ", ".join(f"({r:.3f}, {n})" for r, n in report), flush=True)


def samples_counted(epoch_result: Dict[str, Any]) -> int:
    """What ``train_epoch`` says it consumed (streamed loader)."""
    return int(round(epoch_result["images_per_sec"] * epoch_result["epoch_time"]))
