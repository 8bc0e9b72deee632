"""Routing load of the worst expert layer: tokens sent to its busiest expert
over the mean over all its experts, averaged over the steps of the window
whose metrics the loop fetched (the program's counters
``moe.load_max_over_mean_sum`` / ``moe.steps_observed``). 1 is balance; the
work of a dropless layer, and so the step's time, follows it. An earlier line
gives the largest single reading since the program started. A program
without the counters reports nothing.
"""

LAYER = "train step"
UNIT = "ratio"
MOVES = "samples_per_s"


def read(window):
    c = window["counters"]
    n = c.get("moe.steps_observed")
    if not n:
        return None
    from tpu_dist.obs import counters  # noqa: PLC0415

    peak = counters.snapshot().get("moe.load_max_over_mean_peak")
    window["say"](f"moe_load_max_over_mean: {n:.0f} steps fetched in the window; "
                  f"largest single reading since the first update {peak}")
    return c["moe.load_max_over_mean_sum"] / n
