"""Share of its roofline causal attention reaches: the least time the chip
could take for the scores and weighted values over the causal half and for
one pass over q, k, v, the output and their gradients
(``models/<reference>.py::attention_work``) over the first chip's traced time
in ops of the program's scope ``attn/causal``, forward and backward, whatever
implements it (a Pallas call or XLA's chain).

A configuration without such a layer, or a program without the scope,
reports nothing.
"""

from benchmarks.harness import scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "mfu"


def read(window):
    work = getattr(scopes.model_file(window), "attention_work", None)
    if work is None:
        return None
    cell = window["cell"]
    sequences = cell.batch_per_chip * window["traced_epoch"]["steps"]
    return scopes.roofline_share(window, "attn/causal", *work(cell.config["arch"], sequences))
