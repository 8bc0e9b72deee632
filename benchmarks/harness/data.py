"""The benchmark's inputs, made from ``--seed``.

One general generator of image-classification data: the data block of a
configuration's file gives the sizes. Copied in spirit from
``tpu_dist/data/synthetic.py::synthetic_cifar`` (uniform random uint8 pixels
and labels, deterministic per seed) with an image size and a seed; random
pixels cost the input path and the chip exactly what real ones do.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def make_dataset(
    n: int, image_size: int, num_classes: int, seed: int, distinct: int = 0
) -> Tuple[np.ndarray, np.ndarray]:
    """``(images uint8 [n, s, s, 3], labels int32 [n])``.

    ``distinct`` > 0 draws that many distinct images and tiles them to ``n``
    (labels stay distinct per index): generating gigabytes of noise would only
    lengthen set-up, while the loader's gather still walks the whole array.
    """
    rng = np.random.default_rng([int(seed), 0x5EED])
    block = n if distinct <= 0 else min(int(distinct), n)
    shape = (block, image_size, image_size, 3)
    raw = np.frombuffer(rng.bytes(int(np.prod(shape))), dtype=np.uint8)
    images = raw.reshape(shape)
    if block < n:
        reps = -(-n // block)
        images = np.concatenate([images] * reps, axis=0)[:n]
    labels = rng.integers(0, num_classes, size=(n,), dtype=np.int32)
    return np.ascontiguousarray(images), labels
