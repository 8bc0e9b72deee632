"""Device time of one step's recomputed forwards: the first chip's self time
inside the traced window in the instructions of a ``jax.checkpoint``'s
``rematted_computation`` (``harness/phases.py``), over the traced steps.

0.0 is a reading (the program recomputes nothing); a program without the
phase table reports nothing.
"""

from benchmarks.harness import phases

LAYER = "train step"
UNIT = "ms"
MOVES = "samples_per_s"


def read(window):
    return phases.phase_ms(window, "recompute")
