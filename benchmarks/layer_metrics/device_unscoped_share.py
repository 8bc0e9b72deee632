"""Share of the first chip's busy time inside the traced window that has no
address: self time in ops under no block's scope of the program's table
(``tpu_dist/obs/hlo_scopes.py::SCOPES``) and in none of ``step/optimizer``,
``step/grad_reduce``, ``step/metrics``, ``data/*`` (``harness/phases.py``):
what ``PERF.md`` 5 called "outside every scope", and XLA's unnamed copies.

Says the ten heaviest ops it counted on an earlier line, by name. A program
without the phase table reports nothing.
"""

from benchmarks.harness import phases

LAYER = "train step"
UNIT = "%"
MOVES = "samples_per_s"


def read(window):
    return phases.unscoped_share(window)
