"""Fused-epoch runner: the whole training epoch as ONE compiled program.

The most TPU-native answer to the reference's epoch loop. CIFAR-100 is
~150 MB as uint8 — it fits in HBM many times over, so instead of streaming
batches from the host (reference: DataLoader worker processes + H2D copies
every step, ``distributed.py:71,88-89``), this path:

* keeps the dataset **device-resident**, uint8, sharded over the ``data``
  axis (each chip owns N/n examples);
* shuffles **on device** each epoch (per-shard permutation from a seeded
  key — the ``set_epoch`` semantics, folded per-device);
* augments **on device**: per-image random crop offsets via ``jax.random``,
  the crop itself as two one-hot shift matmuls (:func:`random_crop`; on
  the v5e XLA fuses them into one op and a layout copy follows, 0.3 ms of
  the 120.5-ms ResNet-18 step at batch 4096), then normalize into the
  compute dtype as one elementwise pass ahead of the first conv;
* runs the epoch as ``lax.scan`` over steps inside one ``jit`` call: ONE
  host dispatch per epoch, zero host↔device traffic, no Python in the loop.

Per-step semantics (grads pmean, SyncBN, optimizer, metrics) are exactly
``tpu_dist.train.step``'s. The trade against the streaming path: shuffling
is within each device's shard rather than global (documented deviation —
equivalent in expectation after the initial global shuffle; reshard
periodically if exact torch semantics matter).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.comm.compat import shard_map
from tpu_dist.data.transforms import CIFAR100_MEAN, CIFAR100_STD
from tpu_dist.nn import functional as F
from tpu_dist.obs import hlo_scopes
from tpu_dist.train.state import TrainState


def put_dataset_on_device(mesh: Mesh, images_u8: np.ndarray, labels: np.ndarray):
    """Shard the uint8 dataset over the data axis (one global shuffle first
    so per-shard shuffling stays representative).

    Multi-host: every process passes the SAME full dataset arrays (CIFAR
    scale — ~150 MB host RAM); each process places only its slice of the
    globally shuffled order onto its local devices.
    """
    n = (len(images_u8) // mesh.devices.size) * mesh.devices.size
    perm = np.random.default_rng(0).permutation(len(images_u8))[:n]
    sharding = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
    if jax.process_count() == 1:
        return (
            jax.device_put(np.ascontiguousarray(images_u8[perm]), sharding),
            jax.device_put(np.ascontiguousarray(labels[perm]), sharding),
        )
    # this process's contiguous slice of the global order
    per_proc = n // jax.process_count()
    lo = jax.process_index() * per_proc
    sel = perm[lo : lo + per_proc]
    return (
        jax.make_array_from_process_local_data(
            sharding, np.ascontiguousarray(images_u8[sel])
        ),
        jax.make_array_from_process_local_data(
            sharding, np.ascontiguousarray(labels[sel])
        ),
    )


def fused_steps_per_epoch(dataset_len: int, global_batch: int) -> int:
    """Scan trips one fused-epoch call runs (floor division — the runner
    drops the ragged tail batch). This is the ``loop_trips`` the cost
    model needs to normalize the fused program's numbers to one step:
    XLA's cost analysis counts the scan body ONCE, so flops/bytes of the
    whole-epoch program are body × trips (``obs/costmodel.py``)."""
    return max(1, int(dataset_len) // int(global_batch))


def random_crop(imgs_u8, offs, pad: int):
    """Per-image crop of the zero-padded batch: image ``i`` of the result is
    ``pad(imgs_u8[i])[offs[i,0]:offs[i,0]+H, offs[i,1]:offs[i,1]+W]``.

    ``imgs_u8`` [B,H,W,C] uint8, ``offs`` [B,2] ints in ``[0, 2*pad]`` (row,
    column). Each shift is a one-hot [H,H] (rows) or [W,W] (columns) matrix
    applied on the MXU. 0..255 are exact in bfloat16 and every output is a
    sum with at most one non-zero term, so the result is exact; a source
    index outside the image matches nothing, which is the zero padding.
    A per-image ``dynamic_slice`` under ``vmap`` is a gather, which the
    v5e's compiler expands into a loop of B trips (75.6 ms of a 196-ms step
    at B=4096, against 0.3 ms for this).
    """
    if imgs_u8.dtype != jnp.uint8:
        raise TypeError(f"random_crop is exact for uint8 only, got {imgs_u8.dtype}")
    _, h, w, _ = imgs_u8.shape

    def shift(off, n):
        # [B,n,n]: output index i reads source index i + off - pad
        src = jnp.arange(n)[:, None] + (off - pad)[:, None, None]
        return (src == jnp.arange(n)).astype(jnp.bfloat16)

    # not offs[:, 0]: that traces to a gather (a trivial one, but the test
    # that keeps per-image gathers out of here could not tell them apart)
    rows = shift(lax.index_in_dim(offs, 0, axis=1, keepdims=False), h)
    cols = shift(lax.index_in_dim(offs, 1, axis=1, keepdims=False), w)
    x = jnp.einsum("bik,bkwc->biwc", rows, imgs_u8.astype(jnp.bfloat16))
    x = jnp.einsum("bjk,bikc->bijc", cols, x)
    return x.astype(jnp.uint8)


def make_fused_epoch(
    model_apply: Callable,
    optimizer,
    mesh: Mesh,
    *,
    batch_per_device: int,
    sync_bn: bool = True,
    compute_dtype=jnp.bfloat16,
    pad: int = 4,
    axis: str = mesh_lib.DATA_AXIS,
    mean: np.ndarray = CIFAR100_MEAN,
    std: np.ndarray = CIFAR100_STD,
    moe_aux_coef: float = 0.01,
    grad_compression: str = "none",
    model_kwargs: dict | None = None,
):
    """Build ``epoch(state, images_u8, labels, lr, epoch_idx) ->
    (state, metrics)`` running every step of the epoch on device.

    ``images_u8``/``labels`` from :func:`put_dataset_on_device`.
    ``grad_compression``: same contract as ``make_train_step`` (bf16 cast
    or int8/int8_ef quantized two-stage wire for the grad reduce — the
    shared helpers in ``train/step.py`` define it ONCE for both paths).
    Under ``int8_ef`` the error-feedback residuals ride the ``lax.scan``
    carry inside ``TrainState.ef`` (build with ``step.init_ef_state``),
    so every step of the fused epoch compensates the previous step's
    quantization error exactly like the streaming path.
    """
    from tpu_dist.train.step import (  # noqa: PLC0415
        _QUANT_KEY_SEED,
        compressed_pmean,
        ef_state_spec,
        validate_grad_compression,
    )

    validate_grad_compression(grad_compression)
    bn_axis = axis if sync_bn else None
    mean_c = jnp.asarray(mean, jnp.float32)
    std_inv_c = jnp.asarray(1.0 / std, jnp.float32)

    def augment(imgs_u8, key):
        """[B,H,W,C] uint8 → normalized compute_dtype, random crop pad=4."""
        offs = jax.random.randint(key, (imgs_u8.shape[0], 2), 0, 2 * pad + 1)
        cropped = random_crop(imgs_u8, offs, pad)
        x = (cropped.astype(jnp.float32) / 255.0 - mean_c) * std_inv_c
        return x.astype(compute_dtype)

    def epoch_local(state: TrainState, images_u8, labels, lr, epoch_idx):
        n_loc = images_u8.shape[0]
        steps = n_loc // batch_per_device
        dev = lax.axis_index(axis)
        base = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), epoch_idx), dev)
        perm = jax.random.permutation(base, n_loc)

        from tpu_dist.train.step import extract_aux_loss  # noqa: PLC0415

        def loss_fn(params, bn_state, x, y):
            p = jax.tree_util.tree_map(lambda t: t.astype(compute_dtype), params)
            logits, new_bn = model_apply(
                p, bn_state, x, train=True, axis_name=bn_axis,
                **(model_kwargs or {})
            )
            new_bn, aux = extract_aux_loss(new_bn)
            loss = F.cross_entropy(logits, y)
            if aux is not None:
                loss = loss + moe_aux_coef * aux.astype(loss.dtype)
            return loss, (new_bn, logits)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def body(state, i):
            with hlo_scopes.scope("data/take_crop"):
                idx = lax.dynamic_slice_in_dim(perm, i * batch_per_device, batch_per_device)
                imgs = jnp.take(images_u8, idx, axis=0)
                ys = jnp.take(labels, idx, axis=0)
                x = augment(imgs, jax.random.fold_in(base, i + 1))

            with hlo_scopes.scope("step/loss_grad"):
                (loss, (new_bn, logits)), grads = grad_fn(state.params, state.bn_state, x, ys)
            with hlo_scopes.scope("step/grad_reduce"):
                # same per-step/per-replica stochastic-rounding stream as the
                # streaming path (step.py::quant_key); no-op for none/bf16
                qkey = jax.random.fold_in(
                    jax.random.fold_in(
                        jax.random.PRNGKey(_QUANT_KEY_SEED), state.step
                    ),
                    dev,
                )
                grads, new_ef = compressed_pmean(
                    grads, axis, grad_compression,
                    key=qkey, ef=state.ef,
                )
            if not sync_bn:
                new_bn = lax.pmean(new_bn, axis)
            with hlo_scopes.scope("step/optimizer"):
                new_params, new_opt = optimizer.update(grads, state.opt_state, state.params, lr)
            with hlo_scopes.scope("step/metrics"):
                c1, c5 = F.topk_correct(logits.astype(jnp.float32), ys, (1, 5))
                metrics = {
                    "loss": lax.pmean(loss, axis),
                    "acc1": lax.psum(c1, axis) / (batch_per_device * lax.psum(1, axis)) * 100.0,
                    "acc5": lax.psum(c5, axis) / (batch_per_device * lax.psum(1, axis)) * 100.0,
                }
            return TrainState(
                new_params, new_bn, new_opt, state.step + 1, new_ef
            ), metrics

        state, ms = lax.scan(body, state, jnp.arange(steps))
        return state, jax.tree_util.tree_map(lambda t: t.mean(), ms)

    # the state is replicated except the (per-replica, data-axis-sharded)
    # error-feedback residuals of the int8_ef wire format
    state_spec = TrainState(
        params=P(), bn_state=P(), opt_state=P(), step=P(),
        ef=ef_state_spec(grad_compression, axis=axis),
    )
    sharded = shard_map(
        epoch_local,
        mesh=mesh,
        in_specs=(state_spec, P(axis), P(axis), P(), P()),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,))


def make_fused_eval(
    model_apply: Callable,
    mesh: Mesh,
    *,
    batch_per_device: int,
    compute_dtype=jnp.bfloat16,
    axis: str = mesh_lib.DATA_AXIS,
    mean: np.ndarray = CIFAR100_MEAN,
    std: np.ndarray = CIFAR100_STD,
    ef_specs=(),
    model_kwargs: dict | None = None,
):
    """Whole-test-set evaluation as ONE jit call over device-resident data.

    ``eval(state, images_u8, labels) -> {loss, top1, top5, count}`` global
    sums — the fused counterpart of ``make_eval_step``: the uint8 test set
    lives sharded in HBM (see :func:`put_dataset_on_device`), a ``lax.scan``
    sweeps it in per-device batches, normalization happens on device, and
    padding slots are masked (exact counts, no double-count — same
    guarantee as the streaming evaluator). Padding convention: label < 0
    marks a padding example (use it to round the dataset up to a multiple
    of the device count before :func:`put_dataset_on_device`); the
    per-device scan tail is padded the same way internally.
    """
    mean_c = jnp.asarray(mean, jnp.float32)
    std_inv_c = jnp.asarray(1.0 / std, jnp.float32)

    def eval_local(state: TrainState, images_u8, labels):
        n_loc = images_u8.shape[0]
        steps = -(-n_loc // batch_per_device)
        pad = steps * batch_per_device - n_loc
        imgs = jnp.pad(images_u8, ((0, pad), (0, 0), (0, 0), (0, 0)))
        lbls = jnp.pad(labels, (0, pad), constant_values=-1)
        p = jax.tree_util.tree_map(lambda t: t.astype(compute_dtype), state.params)

        def body(acc, i):
            sl = lambda t: lax.dynamic_slice_in_dim(t, i * batch_per_device, batch_per_device)
            x = (sl(imgs).astype(jnp.float32) / 255.0 - mean_c) * std_inv_c
            logits, _ = model_apply(
                p, state.bn_state, x.astype(compute_dtype), train=False,
                axis_name=None, **(model_kwargs or {})
            )
            y = sl(lbls)
            m = (y >= 0).astype(jnp.float32)
            y = jnp.maximum(y, 0)  # safe index for the masked loss
            nll = F.cross_entropy(logits, y, reduction="none")
            maxk = min(5, logits.shape[-1])
            _, pred = lax.top_k(logits.astype(jnp.float32), maxk)
            hits = (pred == y[:, None]).astype(jnp.float32) * m[:, None]
            acc = {
                "loss": acc["loss"] + jnp.sum(nll * m),
                "top1": acc["top1"] + jnp.sum(hits[:, :1]),
                "top5": acc["top5"] + jnp.sum(hits[:, :maxk]),
                "count": acc["count"] + jnp.sum(m),
            }
            return acc, None

        zero = {k: jnp.zeros((), jnp.float32) for k in ("loss", "top1", "top5", "count")}
        sums, _ = lax.scan(body, zero, jnp.arange(steps))
        return jax.tree_util.tree_map(lambda t: lax.psum(t, axis), sums)

    # ``ef_specs``: layout of the int8_ef residuals when the training state
    # carries them (eval never reads them; the in_specs must still match)
    state_spec = TrainState(
        params=P(), bn_state=P(), opt_state=P(), step=P(), ef=ef_specs
    )
    sharded = shard_map(
        eval_local,
        mesh=mesh,
        in_specs=(state_spec, P(axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)
