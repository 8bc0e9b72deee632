"""Share of the bf16 peak reached inside convolution and dot ops: analytic
forward+backward operations of the traced steps over the first chip's device
time in ops that are a convolution or a dot or a fusion that holds one (the
compiled program's text says which, ``harness/trace.py::matmul_computations``),
over the peak.

Where such ops cannot be told apart, nothing is reported ("not measured").
These fusions also hold the elementwise work XLA fused into them, so the
share is a floor for the matrix unit's own. An earlier line says how much of
the busy time they take: a small share beside a low ``mfu`` means the step is
bound by memory-bound ops outside them, not by the matrix unit.
"""

LAYER = "kernels"
UNIT = "%"
MOVES = "mfu"


def read(window):
    tr, cell, peaks = window["trace"], window["cell"], window["peaks"]
    matmul_s = tr["chip0_matmul_s"]
    if not matmul_s:
        return None
    flops = window["flops_per_sample"] * cell.batch_per_chip * window["traced_epoch"]["steps"]
    t_compute = flops / float(peaks["bf16_flops_per_s"])
    window["say"](
        f"mxu_roofline_share: the operations need {t_compute:.4f} s at the peak; ops holding a "
        f"convolution or dot took {matmul_s:.4f} s = {100 * matmul_s / tr['chip0_busy_s']:.1f}% "
        f"of the chip's {tr['chip0_busy_s']:.4f} s busy"
    )
    return 100.0 * t_compute / matmul_s
