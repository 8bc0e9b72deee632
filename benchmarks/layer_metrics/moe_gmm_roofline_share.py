"""Share of its roofline the experts' grouped product reaches: the least
time the chip could take for the two products over the rows that reached a
held expert (``models/<reference>.py::gmm_work``) over the first chip's traced
time in ops of the program's scope ``moe/experts`` (sort, gather, the two
grouped products, scatter-add), forward and backward.

The live rows come from the program's counters ``moe.rows_live`` and
``lm.tokens``, summed over the steps whose metrics the loop fetched: their
ratio, rows a token, times the traced tokens. An earlier line prints live
over balanced rows. Without the counters nothing is reported.
"""

from benchmarks.harness import scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "mfu"


def read(window):
    work = getattr(scopes.model_file(window), "gmm_work", None)
    c = window["counters"]
    if work is None or not c.get("moe.rows_live") or not c.get("lm.tokens"):
        return None
    cell, steps = window["cell"], window["traced_epoch"]["steps"]
    tokens = cell.batch_per_chip * int(cell.config["arch"]["seq_len"]) * steps
    live = c["moe.rows_live"] / c["lm.tokens"] * tokens
    window["say"](
        f"moe/experts: {c['moe.rows_live'] / c['lm.tokens']:.4f} live rows a token over the "
        f"fetched steps = {c['moe.rows_live'] / c.get('moe.rows_balanced', float('nan')):.4f} "
        f"of the balanced share; {c.get('moe.rows_over_cap', 0):.0f} rows over the buffer"
    )
    return scopes.roofline_share(window, "moe/experts", *work(cell.config["arch"], live))
