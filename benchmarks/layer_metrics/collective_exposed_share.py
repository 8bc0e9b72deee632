"""Share of the traced window in which the first chip's op line is occupied
by a collective (all-reduce, reduce-scatter, all-gather, ... in their start,
done and sync forms). The line runs one op at a time, so this is time in
which compute did not run: the most that overlap or compression can buy.
"""

LAYER = "comm"
UNIT = "%"
MOVES = "samples_per_s"


def read(window):
    tr = window["trace"]
    return 100.0 * tr["chip0_collective_s"] / tr["window_s"]
