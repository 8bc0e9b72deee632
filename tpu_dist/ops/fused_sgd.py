"""Pallas fused SGD+momentum+weight-decay update kernel.

TPU-native equivalent of apex's fused multi-tensor optimizer kernels
(SURVEY §2.2 N4: ``amp.initialize``'s C++/CUDA fused ops). One pass over
each parameter tensor computes

    g' = g + wd * p
    b' = mu * b + g'
    p' = p - lr * b'

reading p/g/b once from HBM and writing p'/b' once — the whole update is
VPU element-wise work tiled through VMEM in (CHUNK, 128) blocks, with the
learning rate prefetched to SMEM. On non-TPU backends (the CPU test mesh)
the same kernel runs in Pallas interpret mode; callers can also just use
the plain jnp update in :class:`tpu_dist.train.optim.SGD` — both paths are
bit-comparable (see tests/test_fused_sgd.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 512  # (512, 128) f32 block = 256 KiB/ref; 5 refs ≈ 1.3 MiB VMEM


def _kernel(lr_ref, p_ref, g_ref, b_ref, out_p_ref, out_b_ref, *, momentum, weight_decay):
    g = g_ref[:] + weight_decay * p_ref[:]
    b = momentum * b_ref[:] + g
    out_b_ref[:] = b
    out_p_ref[:] = p_ref[:] - lr_ref[0] * b


def fused_sgd_leaf(p, g, b, lr, *, momentum: float = 0.9, weight_decay: float = 1e-4,
                   interpret: bool | None = None):
    """Update one parameter leaf. Returns ``(new_p, new_b)``.

    Accepts any shape; internally flattened and padded to (rows, 128) tiles.
    ``interpret=None`` auto-selects interpret mode off-TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    orig_shape, orig_dtype = p.shape, p.dtype
    n = p.size
    cols = _LANES
    rows_per_block = min(_SUBLANES, max(8, -(-n // cols)))
    block = rows_per_block * cols
    n_blocks = -(-n // block)
    padded = n_blocks * block

    def prep(x):
        x = x.reshape(-1).astype(jnp.float32)
        if padded != n:
            x = jnp.pad(x, (0, padded - n))
        return x.reshape(n_blocks * rows_per_block, cols)

    pf, gf, bf = prep(p), prep(g), prep(b)
    lr_arr = jnp.asarray([lr], jnp.float32)

    kernel = functools.partial(_kernel, momentum=momentum, weight_decay=weight_decay)
    blockspec = pl.BlockSpec(
        (rows_per_block, cols), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    out_p, out_b = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lr, whole (1,) array
            blockspec,
            blockspec,
            blockspec,
        ],
        out_specs=[blockspec, blockspec],
        out_shape=[
            jax.ShapeDtypeStruct(pf.shape, jnp.float32),
            jax.ShapeDtypeStruct(bf.shape, jnp.float32),
        ],
        interpret=interpret,
    )(lr_arr, pf, gf, bf)

    def unprep(x):
        return x.reshape(-1)[:n].reshape(orig_shape).astype(orig_dtype)

    return unprep(out_p), unprep(out_b)
