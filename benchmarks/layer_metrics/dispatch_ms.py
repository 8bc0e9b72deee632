"""Host time inside the ``train_step`` call, per step.

Source: the program's phase split (``dispatch_s``), over the window without
its traced epoch. It is "host inside the
call", which includes back-pressure from the device's queue when the device is
the bottleneck: read it under that name, not as enqueue cost.
"""

LAYER = "host loop"
UNIT = "ms"
MOVES = "samples_per_s"


def read(window):
    if window["cell"].fused or not window["steps"]:
        return None
    return 1e3 * sum(e["dispatch_s"] for e in window["epochs"]) / window["steps"]
