#!/usr/bin/env python
"""Build synthetic LMDB datasets so every example runs with zero downloads.

Generates class-template-plus-noise images (learnable, so loss curves are
meaningful) in the shapes of MNIST / CIFAR-10 / ILSVRC12 and writes train/test
LMDBs + a mean binaryproto where the example expects them. Swap in real
datasets (convert_imageset / partition_data) for accuracy-parity runs.

Usage: python examples/make_synthetic_db.py [mnist|cifar10|imagenet] [--train N] [--test N] [--seed S]
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from poseidon_tpu.data.lmdb_reader import LMDBWriter  # noqa: E402
from poseidon_tpu.proto.wire import Datum, encode_blob, encode_datum  # noqa: E402

SPECS = {
    "mnist": dict(shape=(1, 28, 28), classes=10,
                  train="examples/mnist/mnist_train_lmdb",
                  test="examples/mnist/mnist_test_lmdb", mean=None),
    "cifar10": dict(shape=(3, 32, 32), classes=10,
                    train="examples/cifar10/cifar10_train_lmdb",
                    test="examples/cifar10/cifar10_test_lmdb",
                    mean="examples/cifar10/mean.binaryproto"),
    "imagenet": dict(shape=(3, 256, 256), classes=1000,
                     train="examples/imagenet/ilsvrc12_train_lmdb",
                     test="examples/imagenet/ilsvrc12_val_lmdb",
                     mean="examples/imagenet/ilsvrc12_mean.binaryproto"),
}


def build(name: str, n_train: int, n_test: int, seed: int = 0,
          side: int = 0, out_dir: str = "") -> dict:
    """Write the train/test LMDBs (+ mean file) for ``name`` from ``seed``
    and return their paths. ``side``/``out_dir`` cut the record size and
    move the outputs (chip_smoke.py's CPU-sized rehearsal); by default
    everything lands where the example prototxts expect it."""
    spec = dict(SPECS[name])
    if side:
        spec["shape"] = (spec["shape"][0], side, side)
    if out_dir:
        for k in ("train", "test", "mean"):
            if spec[k]:
                spec[k] = os.path.join(out_dir, os.path.basename(spec[k]))
    shape, classes = spec["shape"], spec["classes"]
    rs = np.random.RandomState(seed)
    templates = rs.randint(60, 196, size=(classes,) + shape)

    def write(path, n, seed_off):
        w = LMDBWriter(path)
        rs2 = np.random.RandomState(seed + seed_off)
        for i in range(n):
            label = int(rs2.randint(classes))
            img = np.clip(templates[label]
                          + rs2.normal(0, 30, size=shape), 0, 255
                          ).astype(np.uint8)
            d = Datum(channels=shape[0], height=shape[1], width=shape[2],
                      data=img.tobytes(), label=label)
            w.put(f"{i:08d}".encode(), encode_datum(d))
        w.close()
        print(f"wrote {n} records -> {path}")

    write(spec["train"], n_train, 1)
    write(spec["test"], n_test, 2)
    if spec["mean"]:
        mean = np.full((1,) + shape, 128.0, np.float32)
        with open(spec["mean"], "wb") as f:
            f.write(encode_blob(mean))
        print(f"wrote mean -> {spec['mean']}")
    return {k: spec[k] for k in ("train", "test", "mean")}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("dataset", choices=list(SPECS) + ["all"])
    ap.add_argument("--train", type=int, default=2000)
    ap.add_argument("--test", type=int, default=400)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    targets = list(SPECS) if args.dataset == "all" else [args.dataset]
    for t in targets:
        build(t, args.train, args.test, seed=args.seed)
