"""Device time of one step's forward pass: the first chip's self time inside
the traced window in the instructions the program classifies as ``forward``
(under ``step/loss_grad``, neither transposed nor recomputed;
``harness/phases.py``), over the traced steps.

Says the whole partition on an earlier line: forward, backward, recompute,
optimizer, reduce, metrics, data, other, and their sum beside
``device_step_ms``. A program without the phase table reports nothing.
"""

from benchmarks.harness import phases

LAYER = "train step"
UNIT = "ms"
MOVES = "samples_per_s"


def read(window):
    phases.say_partition(window)
    return phases.phase_ms(window, "forward")
