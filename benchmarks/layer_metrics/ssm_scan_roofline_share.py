"""Share of its roofline the mixers' recurrence reaches: the least time the
chip could take for the chunked scan's operations and bytes
(``models/<reference>.py::scan_work``: the chunked form at the configured
chunk, x, B, C, dt read and y written once each way) over the first chip's
traced time in ops of the program's scope ``ssm/scan``, forward and backward.

A configuration without mixers, or a program without the scope, reports
nothing.
"""

from benchmarks.harness import scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "mfu"


def read(window):
    work = getattr(scopes.model_file(window), "scan_work", None)
    if work is None:
        return None
    cell, steps = window["cell"], window["traced_epoch"]["steps"]
    tokens = cell.batch_per_chip * int(cell.config["arch"]["seq_len"]) * steps
    return scopes.roofline_share(window, "ssm/scan", *work(cell.config["arch"], tokens))
