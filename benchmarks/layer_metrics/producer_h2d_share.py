"""Share of the window's wall time the loader's producer thread spent inside
``mesh.shard_batch``: the ``device_put`` of the host batch (float32 images and
labels) onto the cell's chips.

High here means fewer bytes over the wire (uint8 images, normalise on the
chip) is what buys input headroom. ``device_put`` may return before the copy
has ended; the rate on the earlier line says which: bytes over these seconds
far above what the host link carries means the call only enqueued.

Source: the program's counters ``loader.h2d_s`` and ``loader.h2d_bytes``,
added on the producer thread, over the window without its traced epoch. A
program without the counters reports nothing.
"""

LAYER = "input"
UNIT = "%"
MOVES = "samples_per_s"


def read(window):
    counters = window["counters"]
    h2d_s = counters.get("loader.h2d_s")
    if window["cell"].fused or not window["wall_s"] or h2d_s is None:
        return None
    if h2d_s > 0:
        gb = counters.get("loader.h2d_bytes", 0) / 1e9
        window["say"](f"producer_h2d_share: {gb:.3f} GB in {h2d_s:.3f} s inside shard_batch "
                      f"= {gb / h2d_s:.2f} GB/s")
    return 100.0 * h2d_s / window["wall_s"]
