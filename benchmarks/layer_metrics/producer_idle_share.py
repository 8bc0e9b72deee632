"""Share of the window's wall time the loader's producer thread spent blocked
on a full queue: the loader outrunning the chip. High here means the input
path is not the limit.

Source: the program's counter ``loader.producer_wait_s``, over the window
without its traced epoch.
"""

LAYER = "input"
UNIT = "%"
MOVES = "samples_per_s"


def read(window):
    if window["cell"].fused or not window["wall_s"]:
        return None
    return 100.0 * window["counters"].get("loader.producer_wait_s", 0.0) / window["wall_s"]
