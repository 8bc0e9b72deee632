"""Device time of one step: the union of op intervals on the first chip's
op line inside the traced window, over the steps run in it.

Source: the profiler's trace (``harness/trace.py``).
"""

LAYER = "train step"
UNIT = "ms"
MOVES = "samples_per_s"


def read(window):
    steps = window["traced_epoch"]["steps"]
    return 1e3 * window["trace"]["chip0_busy_s"] / steps if steps else None
