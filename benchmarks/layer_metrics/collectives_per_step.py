"""Collective ops executed per step on the first chip: an exact count from
the trace's op line over the steps of the traced window.
"""

LAYER = "comm"
UNIT = "ops"
MOVES = "samples_per_s"


def read(window):
    steps = window["traced_epoch"]["steps"]
    return window["trace"]["chip0_collectives"] / steps if steps else None
