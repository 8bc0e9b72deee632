"""Share of the traced window in which no op ran, averaged over the cell's
chips (the busiest and the least busy chip are printed on an earlier line).
"""

LAYER = "device"
UNIT = "%"
MOVES = "samples_per_s"


def read(window):
    tr = window["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
