"""Faults planted into the program's first update for the LFM2-MoE cells:
what ``benchmarks/control.py`` runs besides the lower-precision reference.

Each entry of ``FAULTS`` is ``(must_fail, plant)``: ``plant()`` is a context
manager under which ``Trainer._build_train_step`` builds a step with the
fault inside; ``must_fail`` says whether the configuration's limits have to
call that step not correct. One the limits cannot see is listed with
``must_fail`` false, so that its reading is printed beside the others and a
later harness that can see it turns the flag (``PERF.md``, Open questions).
"""

from __future__ import annotations

import dataclasses

import jax

from benchmarks.controls.nemotron_h import _patched, expert_skipped  # the same layer, the same fault
from tpu_dist.nn import nemotron_h as decoder
from tpu_dist.parallel import expert as expert_lib


def expert_gate_dropped():
    """The experts lose their gate's product: ``silu(x W_1) W_2``."""
    inner = expert_lib.dropless_experts

    def ungated(x, chosen, weights, w_up, w_down, *, w_gate, **kw):
        return inner(x, chosen, weights, w_gate, w_down, **{**kw, "activation": jax.nn.silu})

    return _patched(expert_lib, "dropless_experts", ungated)


def conv_tap_dropped():
    """The short convolution without its earliest tap (``k_0 = 0``)."""
    inner = decoder.HybridDecoderDef._short_conv

    def two_taps(self, p, h, dtype):
        return inner(self, {**p, "conv_w": p["conv_w"].at[0].set(0)}, h, dtype)

    return _patched(decoder.HybridDecoderDef, "_short_conv", two_taps)


def _without(**fields):
    """``_norm_rotate`` of a definition with ``fields`` replaced."""
    inner = decoder.HybridDecoderDef._norm_rotate

    def changed(self, scale, x):
        return inner(dataclasses.replace(self, **fields), scale, x)

    return _patched(decoder.HybridDecoderDef, "_norm_rotate", changed)


def no_rotation():
    """Attention without positions: q and k normed and not rotated."""
    return _without(rope_theta=None)


def no_qk_norm():
    """Attention without the per-head RMSNorm of q and k: rotated only."""
    return _without(qk_norm=False)


FAULTS = {
    "expert_skipped": (True, expert_skipped),
    "expert_gate_dropped": (True, expert_gate_dropped),
    "conv_tap_dropped": (True, conv_tap_dropped),
    # one attention block's 10.5M parameters among 469M: the missing rotation
    # still reads 0.999397 over all elements (6.0e-4 flipped against 3.3e-5)
    "no_rotation": (True, no_rotation),
    # 0.9999249 against 0.9999674-0.9999705 as it is (my chip run, PR 35):
    # 2.3 times the sound runs' flipped signs, inside any limit that leaves
    # them room; leaf by leaf q_norm and k_norm read 0.0
    "no_qk_norm": (False, no_qk_norm),
}
