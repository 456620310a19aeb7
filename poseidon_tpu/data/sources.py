"""Data sources: the host-side counterparts of the reference's data layers.

Each source yields (image_array, label) records — or, for a token file
(``HDF5Source`` over integer 2-D ``data`` and ``label``), (ids, targets)
sequences; batching, augmentation and
device transfer are layered on top (pipeline.py). Backends mirror the layer
catalog: DATA (LMDB and LevelDB via our readers), IMAGE_DATA (file lists +
PIL/cv2 decode), HDF5_DATA, MEMORY_DATA, plus synthetic sources for
benchmarks. Reference: ``src/caffe/layers/{data,image_data,hdf5_data,
memory_data}_layer.cpp`` and ``include/caffe/data_layers.hpp:73-122``.
"""

from __future__ import annotations

import os
from typing import List, Tuple

import numpy as np

from ..proto.wire import decode_datum


class Source:
    """Random-access record source."""

    def __len__(self) -> int:
        raise NotImplementedError

    def read(self, index: int) -> Tuple[np.ndarray, int]:
        """-> ((C, H, W) float32 raw values, int label)."""
        raise NotImplementedError

    @property
    def record_shape(self) -> Tuple[int, int, int]:
        arr, _ = self.read(0)
        return tuple(arr.shape)  # type: ignore[return-value]

    # a token source's record is a SEQUENCE of ids with a target per
    # position: int32 both, no image transform, label shaped like the data
    tokens = False


class LMDBSource(Source):
    def __init__(self, path: str):
        from .lmdb_reader import LMDBReader
        self.db = LMDBReader(path)

    def __len__(self) -> int:
        return len(self.db)

    def read(self, index: int) -> Tuple[np.ndarray, int]:
        d = decode_datum(self.db.value_at(index))
        return d.to_array(), d.label


class LevelDBSource(Source):
    """DATA backend LEVELDB (the caffe.proto default), via the pure-Python
    SSTable/log/manifest reader in leveldb_reader.py."""

    def __init__(self, path: str):
        from .leveldb_reader import LevelDBReader
        self.db = LevelDBReader(path)

    def __len__(self) -> int:
        return len(self.db)

    def read(self, index: int) -> Tuple[np.ndarray, int]:
        d = decode_datum(self.db.value_at(index))
        return d.to_array(), d.label


class ImageListSource(Source):
    """IMAGE_DATA: a text file of '<path> <label>' lines, decoded on read."""

    def __init__(self, source: str, root_folder: str = "",
                 new_height: int = 0, new_width: int = 0,
                 shuffle: bool = False, seed: int = 0,
                 color: bool = True):
        self.entries: List[Tuple[str, int]] = []
        with open(source) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                path, label = line.rsplit(None, 1)
                self.entries.append((os.path.join(root_folder, path),
                                     int(label)))
        if shuffle:
            np.random.RandomState(seed).shuffle(self.entries)
        self.new_height = new_height
        self.new_width = new_width
        self.color = color

    def __len__(self) -> int:
        return len(self.entries)

    def read(self, index: int) -> Tuple[np.ndarray, int]:
        from PIL import Image
        path, label = self.entries[index]
        img = Image.open(path)
        img = img.convert("RGB" if self.color else "L")
        if self.new_height and self.new_width:
            img = img.resize((self.new_width, self.new_height))
        arr = np.asarray(img, np.float32)
        if arr.ndim == 2:
            arr = arr[:, :, None]
        # Caffe stores images BGR, HWC -> CHW
        arr = arr[:, :, ::-1] if self.color else arr
        return np.ascontiguousarray(arr.transpose(2, 0, 1)), label


class HDF5Source(Source):
    """HDF5_DATA: 'source' is a text file listing .h5 files with datasets
    'data' and 'label' (hdf5_data_layer.cpp).

    A file whose ``data`` is a 2-D INTEGER dataset with a ``label`` of the
    same shape is a token file: record i is (``seq_len`` ids, ``seq_len``
    targets), both int32 — ids reach the device as integers."""

    def __init__(self, source: str):
        import h5py
        with open(source) as f:
            names = [l.strip() for l in f if l.strip()]
        data: List[np.ndarray] = []
        labels: List[np.ndarray] = []
        kinds = set()
        for name in names:
            with h5py.File(name, "r") as h:
                d, lab = h["data"], h["label"]
                tok = (d.ndim == 2 and np.issubdtype(d.dtype, np.integer)
                       and lab.shape == d.shape)
                kinds.add(tok)
                data.append(np.asarray(d, np.int32 if tok else np.float32))
                labels.append(np.asarray(lab, np.int32) if tok
                              else np.asarray(lab).reshape(-1))
        if len(kinds) > 1:
            raise ValueError(f"{source}: token files and image files mixed")
        self.tokens = kinds == {True}
        self.data_cat = np.concatenate(data)
        self.labels_cat = np.concatenate(labels)

    def __len__(self) -> int:
        return len(self.data_cat)

    def read(self, index: int):
        if self.tokens:
            return self.data_cat[index], self.labels_cat[index]
        arr = self.data_cat[index]
        if arr.ndim == 1:
            arr = arr[:, None, None]
        return arr, int(self.labels_cat[index])


class MemorySource(Source):
    """MEMORY_DATA: arrays handed in by the caller (memory_data_layer.cpp)."""

    def __init__(self, data: np.ndarray, labels: np.ndarray):
        self.data = np.asarray(data, np.float32)
        self.labels = np.asarray(labels).reshape(-1)
        if len(self.data) != len(self.labels):
            raise ValueError("data/label count mismatch")

    def __len__(self) -> int:
        return len(self.data)

    def read(self, index: int) -> Tuple[np.ndarray, int]:
        return self.data[index], int(self.labels[index])


class SyntheticSource(Source):
    """Deterministic learnable task for tests/benchmarks: class templates plus
    Gaussian noise."""

    def __init__(self, shape: Tuple[int, int, int], num_classes: int,
                 size: int = 1 << 16, noise: float = 0.3, seed: int = 0):
        rs = np.random.RandomState(seed)
        self.templates = rs.randn(num_classes, *shape).astype(np.float32)
        self.noise = noise
        self.size = size
        self.num_classes = num_classes
        self.shape = shape

    def __len__(self) -> int:
        return self.size

    def read(self, index: int) -> Tuple[np.ndarray, int]:
        rs = np.random.RandomState(index)
        label = index % self.num_classes
        return (self.templates[label]
                + self.noise * rs.randn(*self.shape).astype(np.float32),
                label)
