"""Faults planted into the program's first update for the Nemotron-H cells:
what ``benchmarks/control.py`` runs besides the lower-precision reference.

Each entry of ``FAULTS`` is ``(must_fail, plant)``: ``plant()`` is a context
manager under which ``Trainer._build_train_step`` builds a step with the
fault inside; ``must_fail`` says whether the configuration's limits have to
call that step not correct. One the limits cannot see is listed with
``must_fail`` false, so that its reading is printed beside the others and a
later harness that can see it turns the flag (``PERF.md``, Open questions).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from tpu_dist.nn import nemotron_h as decoder
from tpu_dist.parallel import expert as expert_lib


@contextlib.contextmanager
def _patched(owner, name, value):
    old = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, old)


def bf16_scan_state():
    """The mixer's decays and carried state in bfloat16 (``ssm_scan``'s own
    ``state_dtype``: real bfloat16 arrays, nothing XLA can fold away)."""
    return _patched(decoder, "ssm_scan",
                    functools.partial(decoder.ssm_scan, state_dtype=jnp.bfloat16))


def bf16_router():
    """Router scores from bfloat16 operands at the default precision."""
    def scores(self, p, h):
        logits = jnp.dot(h.astype(jnp.bfloat16), p["router"].astype(jnp.bfloat16))
        return jax.nn.sigmoid(logits).astype(jnp.float32)

    return _patched(decoder.HybridDecoderDef, "router_scores", scores)


def expert_skipped():
    """The last held expert adds nothing (its down projection zeroed)."""
    inner = expert_lib.dropless_experts

    def skipping(x, chosen, weights, w_up, w_down, **kw):
        return inner(x, chosen, weights, w_up, w_down.at[-1].set(0), **kw)

    return _patched(expert_lib, "dropless_experts", skipping)


FAULTS = {
    "bf16_scan_state": (True, bf16_scan_state),
    "expert_skipped": (True, expert_skipped),
    # inside the bf16 policy's own scatter at the first update: 0.999980
    # against 0.999974-0.999982 as it is (my chip run, PR 33): its leaves are
    # 1M of 528M elements, which the one number over all elements hides
    "bf16_router": (False, bf16_router),
}
