"""Share of the bf16 peak reached inside the ops that run matrix products:
analytic forward+backward operations of the traced steps over the first
chip's device time in every op that holds a matrix product, whichever
implements it, over the peak. Those ops are XLA's convolutions and dots and
the fusions that hold one, and the Pallas kernels whose body holds one (the
compiled program's text says which, ``harness/trace.py::matmul_computations``).
So the same work reads the same share whether XLA or a kernel runs it. The
analytic count is the multiply-accumulates of matrix products, each run in one
of those ops, so the share stays under 100: a reading over it means an op that
runs a product was left out of the time, or the count is too high.

Where such ops cannot be told apart, nothing is reported ("not measured").
These ops also hold the elementwise work fused or written into them, so the
share is a floor for the matrix unit's own. An earlier line says how much of
the busy time they take, XLA's and each kernel's by its name: a small share
beside a low ``mfu`` means the step is bound by ops outside them, not by the
matrix unit.
"""

LAYER = "kernels"
UNIT = "%"
MOVES = "mfu"


def _seconds(parts, busy):
    return ", ".join(f"{stem} {t:.4f} s ({100 * t / busy:.1f}%)"
                     for stem, t in sorted(parts.items(), key=lambda kv: -kv[1])) or "none"


def read(window):
    tr, cell, peaks = window["trace"], window["cell"], window["peaks"]
    matmul_s, split, busy = tr["chip0_matmul_s"], tr["chip0_matmul_split_s"], tr["chip0_busy_s"]
    if not matmul_s:
        return None
    flops = window["flops_per_sample"] * cell.batch_per_chip * window["traced_epoch"]["steps"]
    t_compute = flops / float(peaks["bf16_flops_per_s"])
    window["say"](
        f"mxu_roofline_share: the operations need {t_compute:.4f} s at the peak; ops holding a "
        f"matrix product took {matmul_s:.4f} s = {100 * matmul_s / busy:.1f}% of the chip's "
        f"{busy:.4f} s busy: XLA's convolution and dot ops {split['xla']:.4f} s "
        f"({100 * split['xla'] / busy:.1f}%), Pallas kernels {sum(split['kernels'].values()):.4f} s "
        f"({100 * sum(split['kernels'].values()) / busy:.1f}%): {_seconds(split['kernels'], busy)}; "
        f"custom calls not counted: {_seconds(split['other_calls'], busy)}"
    )
    return 100.0 * t_compute / matmul_s
