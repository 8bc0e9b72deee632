"""The comparison that decides ``correct``: the program's loss at step 0 and
its first parameter update against the configuration's plain float32
reference and that reference's own loss (``benchmarks/models/<reference>.py``),
on one device.

Tolerances sit in the configuration's file (``reference_check``), each with
its reason, because how far bf16 strays from float32 is a property of the
model. They are set from what the chip shows for bf16 and are a small multiple
of it, so a step computed in fp8, or with a term dropped, fails.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def default_loss_sum(model):
    """The loss of a model file that states none: softmax cross-entropy of
    ``model.logits`` against one class label a sample, summed over the chunk."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    def loss_sum(arch, params, inputs, targets):
        logp = jax.nn.log_softmax(model.logits(arch, params, inputs), axis=-1)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()

    return loss_sum


def reference_program(model, arch, n: int, chunk: int):
    """``(jitted, state)``: the reference's program over a batch of ``n``
    samples, ``jitted(params, state, inputs, targets) -> (mean loss,
    gradient)``, and the ``state`` of ``arch`` to hand it. Call it under
    ``jax.default_matmul_precision("highest")``.

    The state is ``arch``'s array-valued entries: what the program's run left
    there for the reference (a router's balanced ``router_bias``,
    ``benchmarks/data/tokens.py``). It is an argument of the program, so that
    the program is the same for every seed of a configuration and the
    persistent cache serves it from the second seed on; closed over, a seed's
    state would be a constant of the program, and every seed would compile
    anew."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    if model.WHOLE_BATCH or chunk <= 0 or chunk >= n:
        chunk = n
    if n % chunk:
        raise ValueError(f"reference chunk {chunk} does not divide batch {n}")
    loss_sum = getattr(model, "loss_sum", None) or default_loss_sum(model)
    state = {k: v for k, v in arch.items() if isinstance(v, (np.ndarray, jax.Array))}
    sizes = {k: v for k, v in arch.items() if k not in state}

    def whole(p, state, x, y):
        a = {**sizes, **state}
        xs = x.reshape((n // chunk, chunk) + x.shape[1:])
        ys = y.reshape((n // chunk, chunk) + y.shape[1:])

        def body(acc, xy):
            loss, grads = jax.value_and_grad(lambda q: loss_sum(a, q, *xy))(p)
            return (acc[0] + loss, jax.tree_util.tree_map(jnp.add, acc[1], grads)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, p))
        (loss, grads), _ = jax.lax.scan(body, zero, (xs, ys))
        return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)

    return jax.jit(whole), state


def reference_loss_and_grads(model, arch, params, inputs, targets, chunk: int, device):
    """The batch's mean loss and its gradient in float32 at the highest matmul
    precision (a TPU otherwise rounds float32 operands to bf16), on one
    device. The loss is the model file's: ``loss_sum(arch, params, inputs,
    targets)``, the sum over a chunk's samples of each sample's loss (one
    class label, every position of a sequence, several exits: the file
    knows); ``default_loss_sum`` where the file gives none. The inputs reach
    it in the file's ``INPUT_DTYPE`` (float32 where it states none), so token
    ids stay integers; targets are int32 with the samples on the leading
    axis. The batch is taken in chunks of ``chunk`` samples where the model
    allows (``WHOLE_BATCH`` false), so float32 activations of a large batch
    need not fit at once; the sum over chunks is exact arithmetic for a loss
    that is a mean over samples. The state in ``arch`` is an argument of the
    program (``reference_program``)."""
    import jax  # noqa: PLC0415

    jitted, state = reference_program(model, arch, int(targets.shape[0]), chunk)
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        loss, grads = jitted(
            put(jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), params)),
            put(state),
            put(np.asarray(inputs, getattr(model, "INPUT_DTYPE", np.float32))),
            put(np.asarray(targets, np.int32)),
        )
    return float(loss), jax.device_get(grads)


def _flat64(tree) -> np.ndarray:
    import jax  # noqa: PLC0415

    return np.concatenate(
        [np.asarray(x, np.float64).ravel() for x in jax.tree_util.tree_leaves(tree)]
    )


def implied_gradient(update: Dict[str, Any], train_config: Dict[str, Any]):
    """What the program's first update says about its gradient.

    SGD with momentum from a zero buffer: ``p1 = p0 - lr (g + wd p0)``, so the
    update gives the gradient back exactly. AdamW from zero moments:
    ``p1 = p0 - lr (g / (|g| + eps) + wd_leaf p0)``, so it gives back the
    gradient's sign (``wd_leaf`` is 0 on leaves of rank <= 1, the program's
    ``auto`` decay mask)."""
    import jax  # noqa: PLC0415

    lr = update["lr"]
    wd = float(train_config.get("weight_decay", 1e-4))
    adamw = train_config.get("optimizer", "sgd") == "adamw"
    if not adamw and train_config.get("optimizer", "sgd") != "sgd":
        raise ValueError("the first-update check knows sgd and adamw")

    def leaf(p0, p1):
        p0, p1 = np.asarray(p0, np.float64), np.asarray(p1, np.float64)
        decay = wd if (not adamw or p0.ndim > 1) else 0.0
        return (p0 - p1) / lr - decay * p0

    return jax.tree_util.tree_map(leaf, update["before"], update["after"]), adamw


def compare(update, ref_loss, ref_grads, train_config, tol) -> Dict[str, Any]:
    """The verdict and the numbers behind it."""
    import jax  # noqa: PLC0415

    implied, sign_only = implied_gradient(update, train_config)
    g_ref, g_prog = _flat64(ref_grads), _flat64(implied)
    loss_err = abs(update["loss"] - ref_loss) / max(abs(ref_loss), 1e-12)
    out: Dict[str, Any] = {
        "loss_program": update["loss"], "loss_reference": ref_loss,
        "loss_rel_err": loss_err, "loss_rel_tol": float(tol["loss_rel_tol"]),
    }
    ok = np.isfinite(loss_err) and loss_err <= float(tol["loss_rel_tol"])
    if sign_only:
        # compare the direction where the reference gradient stands clear of
        # rounding: above `sign_floor_rms` times the gradient's RMS
        floor = float(tol["sign_floor_rms"]) * float(np.sqrt(np.mean(g_ref ** 2)))
        big = np.abs(g_ref) > floor
        agree = float(np.mean(np.sign(g_prog[big]) == np.sign(g_ref[big]))) if big.any() else 0.0
        out.update(sign_agreement=agree, sign_agreement_min=float(tol["sign_agreement_min"]),
                   sign_compared_share=float(big.mean()))
        ok = ok and agree >= float(tol["sign_agreement_min"])
    else:
        err = float(np.linalg.norm(g_prog - g_ref) / max(np.linalg.norm(g_ref), 1e-30))
        # leaf by leaf, so that a term dropped from one small tensor shows:
        # the cosine between the two gradients of every leaf that carries at
        # least a thousandth of the gradient's norm
        total = float(np.linalg.norm(g_ref))
        worst = 1.0
        for a, b in zip(jax.tree_util.tree_leaves(implied), jax.tree_util.tree_leaves(ref_grads)):
            a, b = np.asarray(a, np.float64).ravel(), np.asarray(b, np.float64).ravel()
            nb = float(np.linalg.norm(b))
            if nb >= 1e-3 * total:
                worst = min(worst, float(a @ b / max(np.linalg.norm(a) * nb, 1e-300)))
        out.update(grad_rel_l2_err=err, grad_rel_l2_tol=float(tol["grad_rel_l2_tol"]),
                   worst_leaf_cosine=worst, leaf_cosine_min=float(tol["leaf_cosine_min"]))
        ok = (ok and np.isfinite(err) and err <= float(tol["grad_rel_l2_tol"])
              and worst >= float(tol["leaf_cosine_min"]))
    out["ok"] = bool(ok)
    return out
