"""Share of its roofline the gated short convolution reaches: the least time
the chip could take for the chain from the split of ``W_in``'s output to ``y``
(``models/<reference>.py::conv_work``: B, C, u read and y written once forward,
dy, B, C, u read and the three gradients written once backward; bf16) over the
first chip's traced time in ops of the program's scope ``conv/short``,
forward and backward, whatever implements it.

A configuration without such blocks, or a program without the scope, reports
nothing.

The cell that brought this reader (``lfm2_24b_a2b.seq8k``) also fills the
scopes and counters of three accepted readers whose ``workloads`` lists it is
not on yet (``PERF.md`` 7.0e): their values go on earlier lines here, under
their own names, and into no result.
"""

from benchmarks.harness import manifest, scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "mfu"
ALSO_SAID = ("moe_gmm_roofline_share", "lm_attn_roofline_share", "moe_load_max_over_mean")


def read(window):
    work = getattr(scopes.model_file(window), "conv_work", None)
    if work is None:
        return None
    cell, steps = window["cell"], window["traced_epoch"]["steps"]
    listed = {m["name"] for m in cell.per_layer}
    for name in ALSO_SAID:
        if name not in listed:
            value = manifest.load_module(cell.root, "layer_metrics", name).read(window)
            window["say"](f"{name} (not on this cell's list): "
                          + ("not measured" if value is None else f"{value:.4f}"))
    tokens = cell.batch_per_chip * int(cell.config["arch"]["seq_len"]) * steps
    return scopes.roofline_share(window, "conv/short", *work(cell.config["arch"], tokens))
