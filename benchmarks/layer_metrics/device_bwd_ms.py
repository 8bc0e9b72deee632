"""Device time of one step's backward pass: the first chip's self time inside
the traced window in the instructions the program classifies as ``backward``
(``transpose(...)`` in their ``op_name``, the recomputed forwards apart;
``harness/phases.py``), over the traced steps. A program without the phase
table reports nothing.
"""

from benchmarks.harness import phases

LAYER = "train step"
UNIT = "ms"
MOVES = "samples_per_s"


def read(window):
    return phases.phase_ms(window, "backward")
