"""Host time per epoch in which the chip has nothing queued: the epoch's head
(entry of ``train_epoch`` to the first ``next()`` of the loader: ``set_epoch``,
lr, meters), its refill (that first ``next()``: the producer thread starts and
makes its first batch) and its tail (after the drain to the return: final
fetch, prints, memory gauges, goodput). The drain itself is not in it: the chip
works through its queue then.

Source: the program's counters ``train.epoch_head_s``, ``train.epoch_refill_s``
and ``train.epoch_tail_s`` over ``train.epochs``, each added where the loop
reads its clock, over the window without its traced epoch. A program without
the counters reports nothing.
"""

LAYER = "host loop"
UNIT = "ms"
MOVES = "samples_per_s"

PARTS = ("head", "refill", "tail")


def read(window):
    counters = window["counters"]
    epochs = counters.get("train.epochs")
    parts = [counters.get(f"train.epoch_{p}_s") for p in PARTS]
    if window["cell"].fused or not epochs or None in parts:
        return None
    window["say"]("epoch_boundary_ms: " + ", ".join(
        f"{p} {1e3 * s / epochs:.2f} ms" for p, s in zip(PARTS, parts))
        + f", drain {1e3 * counters.get('train.epoch_drain_s', 0.0) / epochs:.2f} ms "
        f"(chip busy), mean of {epochs} epoch(s)")
    return 1e3 * sum(parts) / epochs
