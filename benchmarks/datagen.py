"""Seeded RecordIO archives for the training cells (after bench.py's
``_make_rec`` / ``_make_raw_rec``): the same seed gives the same bytes."""

from concurrent.futures import ThreadPoolExecutor
import os

import numpy as np


def _image(seed: int, i: int, size: int) -> np.ndarray:
    """A low-frequency field like a photograph's: a random 8x8 grid
    resized up (cubic), plus noise of +-8. Uniform noise is a JPEG
    decoder's worst case, and nobody trains on it."""
    import cv2
    rng = np.random.default_rng([seed, i])
    grid = rng.integers(0, 256, (8, 8, 3)).astype(np.float32)
    img = cv2.resize(grid, (size, size), interpolation=cv2.INTER_CUBIC)
    img += rng.integers(-8, 9, (size, size, 3))
    return np.clip(img, 0, 255).astype(np.uint8)


def make_rec(path: str, n: int, size: int, seed: int, fmt: str,
             nclass: int = 1000) -> None:
    """Write ``n`` records of ``size`` x ``size`` images, labels
    ``i % nclass``: ``fmt`` ``jpeg`` (what im2rec archives hold) or
    ``raw`` (uint8 tensors, no decode)."""
    import cv2
    from cxxnet_tpu.io.recordio import (RecordIOWriter, pack_image_record,
                                        pack_raw_tensor_record)

    def encode(i: int) -> bytes:
        img = _image(seed, i, size)
        label = float(i % nclass)
        if fmt == "raw":
            return pack_raw_tensor_record(i, label, img)
        ok, buf = cv2.imencode(".jpg", img)
        if not ok:
            raise RuntimeError("cv2.imencode failed on record %d" % i)
        return pack_image_record(i, label, buf.tobytes())

    writer = RecordIOWriter(path)
    # cv2 releases the interpreter lock; records are written in order
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        for rec in pool.map(encode, range(n)):
            writer.write_record(rec)
    writer.close()
