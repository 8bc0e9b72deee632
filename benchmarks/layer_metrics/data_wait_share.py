"""Share of the window's wall time the step loop spent blocked on the loader.

Source: the program's own phase split (``Trainer.train_epoch`` ->
``data_wait_s``, a host clock around ``next(loader)``), summed over the
window's epochs (without the traced one: the profiler slows the loader). A fused cell has no loader and reports nothing.
"""

LAYER = "input"
UNIT = "%"
MOVES = "samples_per_s"


def read(window):
    if window["cell"].fused or not window["wall_s"]:
        return None
    return 100.0 * sum(e["data_wait_s"] for e in window["epochs"]) / window["wall_s"]
