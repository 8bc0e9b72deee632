"""Share of the window's wall time the loader's producer thread spent making
the host batch: the ``next()`` of ``DataLoader._host_batches`` (index slice,
C++ gather + crop + flip + normalise, labels).

With ``producer_h2d_share`` and ``producer_idle_share`` it accounts for the
thread's life (the clocks are chained), which is not the window's: a thread is
started each epoch and ends when the epoch's last batch is queued, so where the
loop runs ahead of the chip the three sum to far less than 100 and the rest is
"no thread: every batch of the epoch is made" (the earlier line says how much).
High here means more gather threads (or less work per image) is what buys
input headroom.

Source: the program's counter ``loader.gather_s``, added on the producer
thread where the work happens, over the window without its traced epoch. A
program without the counter reports nothing.
"""

LAYER = "input"
UNIT = "%"
MOVES = "samples_per_s"


def read(window):
    counters = window["counters"]
    gather_s = counters.get("loader.gather_s")
    if window["cell"].fused or not window["wall_s"] or gather_s is None:
        return None
    alive_s = gather_s + counters.get("loader.h2d_s", 0.0) + counters.get("loader.producer_wait_s", 0.0)
    window["say"](f"producer_gather_share: the producer thread exists for {alive_s:.3f} s of the "
                  f"window's {window['wall_s']:.3f} s ({100.0 * alive_s / window['wall_s']:.1f}%): "
                  "gather + h2d + full queue; for the rest the epoch's batches are all made")
    return 100.0 * gather_s / window["wall_s"]
