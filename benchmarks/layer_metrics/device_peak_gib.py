"""Largest allocator peak over the cell's chips, where it is not an
end-to-end metric: memory spent to buy speed shows here.
"""

LAYER = "device"
UNIT = "GiB"
MOVES = "samples_per_s"


def read(window):
    return window["peak_bytes"] / float(1 << 30) if window["peak_bytes"] else None
