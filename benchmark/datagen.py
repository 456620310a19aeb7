"""Seeded synthetic inputs: the LMDB a cell's DATA layer reads, its mean file.

Records are what ILSVRC12's ``convert_imageset --resize_height=256
--resize_width=256`` leaves: ``side`` x ``side`` x 3 bytes and an integer
label, keyed ``%08d``. Pixel content is uniform random bytes from the seed —
the reader, mirror, crop and mean-subtract do the same work on any bytes, and
random bytes are made at GB/s, which keeps set-up short. The files are written
with the program's own dataset tools (``LMDBWriter``, ``encode_datum``,
``encode_blob``), as a user's ``convert_imageset`` would.

A database is reused by a later run that asks for exactly the same thing (the
stamp says what it holds) and rebuilt otherwise, in place: one database per
cell, never one per seed, so a checkout's disk use stays bounded.
"""

from __future__ import annotations

import json
import os
import shutil

import numpy as np

STAMP = "stamp.json"


def build_lmdb(out_dir: str, *, records: int, side: int, channels: int,
               classes: int, seed: int) -> dict:
    """Make ``out_dir/train_lmdb`` and ``out_dir/mean.binaryproto`` hold
    ``records`` seeded records; returns their paths and whether this call
    built them."""
    want = {"records": records, "side": side, "channels": channels,
            "classes": classes, "seed": seed, "format": 1}
    paths = {"train": os.path.join(out_dir, "train_lmdb"),
             "mean": os.path.join(out_dir, "mean.binaryproto")}
    stamp = os.path.join(out_dir, STAMP)
    try:
        with open(stamp) as f:
            if json.load(f) == want:
                return dict(paths, built=False)
    except (OSError, ValueError):
        pass
    from poseidon_tpu.data.lmdb_reader import LMDBWriter
    from poseidon_tpu.proto.wire import Datum, encode_blob, encode_datum

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    rng = np.random.default_rng(seed)
    size = channels * side * side
    pixels = rng.integers(0, 256, size=(records, size), dtype=np.uint8)
    labels = rng.integers(0, classes, size=records)
    writer = LMDBWriter(paths["train"])
    for i in range(records):
        writer.put(f"{i:08d}".encode(), encode_datum(Datum(
            channels=channels, height=side, width=side,
            data=pixels[i].tobytes(), label=int(labels[i]))))
    writer.close()
    with open(paths["mean"], "wb") as f:
        f.write(encode_blob(np.full((1, channels, side, side), 128.0,
                                    np.float32)))
    # the stamp goes last: a build that died leaves none and is redone
    with open(stamp, "w") as f:
        json.dump(want, f)
    return dict(paths, built=True)
