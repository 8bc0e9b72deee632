"""Loss and classification functionals (the ``nn.CrossEntropyLoss`` /
``accuracy`` surface of the reference, ``distributed.py:62`` and
``utils/util.py:50-64``), written to fuse cleanly under jit."""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def cross_entropy(logits, labels, *, reduction: str = "mean", label_smoothing: float = 0.0):
    """Softmax cross-entropy with integer labels (optionally smoothed).

    Computed in f32 regardless of the compute dtype: the log-sum-exp is the
    numerically fragile spot under bf16. ``label_smoothing=s`` mixes the
    one-hot target with the uniform distribution (torch semantics).
    """
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    if label_smoothing > 0.0:
        s = label_smoothing
        uniform = -logp.mean(axis=-1)
        nll = (1.0 - s) * nll + s * uniform
    if reduction == "mean":
        return nll.mean()
    if reduction == "sum":
        return nll.sum()
    return nll


def topk_correct(logits, labels, ks: Sequence[int] = (1, 5)):
    """Per-batch counts of top-k hits — the core of the reference's
    ``accuracy(output, target, topk)`` (``utils/util.py:50-64``), returned as
    counts (not percentages) so shards can be summed exactly across replicas.
    """
    maxk = min(max(ks), logits.shape[-1])  # clamp: num_classes may be < 5
    _, pred = lax.top_k(logits, maxk)  # [B, maxk]
    hits = pred == labels[:, None]
    return tuple(jnp.sum(hits[:, : min(k, maxk)]) for k in ks)


def accuracy(logits, labels, topk: Sequence[int] = (1,)) -> Tuple:
    """Percentages, reference signature (``utils/util.py:50``)."""
    counts = topk_correct(logits, labels, topk)
    b = logits.shape[0]
    return tuple(c.astype(jnp.float32) * (100.0 / b) for c in counts)


def blocked_cross_entropy(hidden, w_head, targets, weights, *, block: int):
    """Head and cross-entropy over the tokens a block at a time: the
    ``[block, vocabulary]`` float32 logits exist for one block only, forward
    and (recomputed) backward. ``hidden [T, d]``, ``w_head [d, V]`` in the
    compute dtype, ``targets [T]``, ``weights [T]`` float32. Returns float32
    sums over the tokens: ``(weighted nll, weighted top-1 hits, top-5 hits)``;
    a hit is counted from the target's rank among the logits, no sort."""
    t = targets.shape[0]
    block = math.gcd(t, block)

    @jax.checkpoint
    def one(h, y, wt):
        logits = jnp.dot(h, w_head, preferred_element_type=jnp.float32)
        at = jnp.take_along_axis(logits, y[:, None], axis=-1)
        nll = jax.nn.logsumexp(logits, axis=-1) - at[:, 0]
        rank = jnp.sum(logits > at, axis=-1)
        return jnp.stack([jnp.sum(nll * wt), jnp.sum((rank < 1) * wt), jnp.sum((rank < 5) * wt)])

    def body(acc, xs):
        return acc + one(*xs), None

    cut = lambda a: a.reshape((t // block, block) + a.shape[1:])  # noqa: E731
    sums, _ = lax.scan(body, jnp.zeros((3,), jnp.float32),
                       (cut(hidden), cut(targets), cut(weights)))
    return sums[0], sums[1], sums[2]
