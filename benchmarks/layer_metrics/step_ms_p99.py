"""99th percentile of the time between two dispatches of the step, over the
window (epoch boundaries included: they are what a user's loop pays).

Source: laps recorded by the harness's wrapper around ``trainer.train_step``
(traced run). With n laps there are about n/100 beyond it: a recorded tail,
not a number that decides a PR.
"""

LAYER = "host loop"
UNIT = "ms"
MOVES = "samples_per_s"


def read(window):
    laps = sorted(window["laps_s"])
    if len(laps) < 20:
        return None
    return 1e3 * laps[min(len(laps) - 1, int(0.99 * len(laps)))]
