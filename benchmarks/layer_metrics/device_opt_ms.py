"""Device time of one step's optimizer: the first chip's self time inside the
traced window in the instructions under the program's ``step/optimizer``
(gradient clipping and the update; ``harness/phases.py``), over the traced
steps.

A fusion counts under the one ``op_name`` it carries, so this reads low where
XLA fuses an update into its weight gradient: the v5e's compiler names such a
fusion after the convolution (ResNet's 3x3x64x64 weight gradients + SGD,
``multiply_subtract_fusion``, 3.4 ms a step each, read as ``backward`` and
leave 0.05 ms here). That edge is the measurement's. A program without the
phase table reports nothing.
"""

from benchmarks.harness import phases

LAYER = "train step"
UNIT = "ms"
MOVES = "samples_per_s"


def read(window):
    return phases.phase_ms(window, "optimizer")
