"""Loss of the 100th step after the warm-up, same seed.

It must repeat exactly on one commit. A PR in which it moves together with
``mfu`` got its speed from changed arithmetic and has to say so. Read from the
handle the harness's wrapper kept (fetched after the window); a window of
fewer than 101 steps, or a fused cell, reports nothing.
"""

LAYER = "train step"
UNIT = "loss"
MOVES = "mfu"


def read(window):
    return window["loss_at"](100)
