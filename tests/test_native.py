"""Native data plane vs Python reference: bit-parity and throughput sanity."""

import os
import shutil

import numpy as np
import pytest

from poseidon_tpu.data import native
from poseidon_tpu.data.lmdb_reader import LMDBWriter
from poseidon_tpu.proto.wire import Datum, encode_datum

# the library is a build output (native/build/ is git-ignored): with a
# compiler present it MUST build from the tracked source — a failed build
# fails test_library_builds_from_source instead of skipping the module
pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="no C++ toolchain")


def test_library_builds_from_source():
    assert native.available()
    assert os.path.getmtime(native._LIB) >= os.path.getmtime(native._SRC)


def test_library_is_stale_when_source_is_newer():
    assert native.available() and not native._stale()
    lib_mtime = os.path.getmtime(native._LIB)
    src_stat = os.stat(native._SRC)
    try:
        os.utime(native._SRC, (lib_mtime + 10, lib_mtime + 10))
        assert native._stale()
    finally:
        os.utime(native._SRC, (src_stat.st_atime, src_stat.st_mtime))
    assert not native._stale()


@pytest.fixture(scope="module")
def datum_db(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("db") / "lmdb")
    w = LMDBWriter(path)
    rs = np.random.RandomState(0)
    arrays, labels = [], []
    for i in range(64):
        arr = rs.randint(0, 255, size=(3, 12, 12)).astype(np.uint8)
        label = int(rs.randint(0, 10))
        arrays.append(arr)
        labels.append(label)
        w.put(f"{i:08d}".encode(),
              encode_datum(Datum(3, 12, 12, arr.tobytes(), label=label)))
    w.close()
    return path, arrays, labels


def test_native_reads_match_python(datum_db):
    path, arrays, labels = datum_db
    b = native.NativeLMDBBatcher(path, train=False)
    assert len(b) == 64
    assert b.record_shape == (3, 12, 12)
    data, got_labels = b.batch(np.arange(64))
    for i in range(64):
        np.testing.assert_array_equal(data[i], arrays[i].astype(np.float32))
        assert got_labels[i] == labels[i]
    b.close()


def test_native_transform_matches_python(datum_db):
    path, arrays, labels = datum_db
    mean_values = np.asarray([10.0, 20.0, 30.0], np.float32)
    b = native.NativeLMDBBatcher(path, crop_size=8, train=False, scale=0.5,
                                 mean_values=mean_values)
    data, _ = b.batch(np.asarray([5]))
    # center crop offset (12-8)//2 = 2
    src = arrays[5].astype(np.float32)[:, 2:10, 2:10]
    want = (src - mean_values[:, None, None]) * 0.5
    np.testing.assert_allclose(data[0], want, rtol=1e-6)
    b.close()


def test_native_full_mean_array(datum_db):
    path, arrays, _ = datum_db
    rs = np.random.RandomState(1)
    mean = rs.rand(3, 12, 12).astype(np.float32)
    b = native.NativeLMDBBatcher(path, crop_size=6, train=False, mean=mean)
    data, _ = b.batch(np.asarray([0]))
    src = arrays[0].astype(np.float32)
    off = (12 - 6) // 2
    want = (src - mean)[:, off:off + 6, off:off + 6]
    np.testing.assert_allclose(data[0], want, rtol=1e-5)
    b.close()


def test_native_train_crops_are_valid_windows(datum_db):
    path, arrays, _ = datum_db
    b = native.NativeLMDBBatcher(path, crop_size=8, mirror=True, train=True)
    data, _ = b.batch(np.arange(8), seed=7)
    for i in range(8):
        src = arrays[i].astype(np.float32)
        ok = False
        for ho in range(5):
            for wo in range(5):
                win = src[:, ho:ho + 8, wo:wo + 8]
                if np.allclose(data[i], win) or \
                        np.allclose(data[i], win[:, :, ::-1]):
                    ok = True
        assert ok, f"record {i}: output is not a crop/mirror of the source"
    # determinism: same seed -> same batch
    data2, _ = b.batch(np.arange(8), seed=7)
    np.testing.assert_array_equal(data, data2)
    # different seed -> different crops (with overwhelming probability)
    data3, _ = b.batch(np.arange(8), seed=8)
    assert not np.array_equal(data, data3)
    b.close()


def test_pipeline_uses_native_for_lmdb_data_layer(datum_db):
    path, _, _ = datum_db
    from poseidon_tpu.data.pipeline import BatchPipeline
    from poseidon_tpu.proto.messages import DataParameter, LayerParameter

    lp = LayerParameter(
        name="d", type="DATA", top=["data", "label"],
        data_param=DataParameter(source=path, batch_size=16, backend="LMDB"))
    pipe = BatchPipeline(lp, "TRAIN", 16)
    assert pipe.native is not None, "native path should engage for LMDB DATA"
    batch = next(pipe)
    assert batch["data"].shape == (16, 3, 12, 12)
    assert batch["label"].dtype == np.int32
    pipe.close()

    # forced Python path produces identically-shaped batches
    pipe_py = BatchPipeline(lp, "TRAIN", 16, use_native=False)
    batch_py = next(pipe_py)
    assert batch_py["data"].shape == batch["data"].shape
    pipe_py.close()


def test_native_snappy_matches_python():
    """The C++ decoder (pdp_snappy_uncompress) against the pure-Python codec
    on literals, hand-crafted copy elements, and malformed streams."""
    from poseidon_tpu.data import snappy
    from poseidon_tpu.data.native import available, snappy_uncompress
    if not available():
        import pytest
        pytest.skip("native dataplane not built")
    rs = np.random.RandomState(1)
    for n in [0, 1, 60, 300, 70000]:
        comp = snappy.compress(rs.bytes(n))
        assert snappy_uncompress(comp) == snappy._uncompress_py(comp)
    # copy-1 back-reference incl. overlapping RLE-style copy
    blob = bytes([8]) + bytes([3 << 2]) + b"abcd" + bytes([1, 4])
    assert snappy_uncompress(blob) == b"abcdabcd"
    blob2 = bytes([8]) + bytes([1 << 2]) + b"ab" + bytes([(2 << 2) | 1, 2])
    assert snappy_uncompress(blob2) == b"abababab"
    # copy-2: literal "xy", copy len 3 offset 2 via 2-byte offset
    blob3 = bytes([5]) + bytes([1 << 2]) + b"xy" + \
        bytes([((3 - 1) << 2) | 2, 2, 0])
    assert snappy_uncompress(blob3) == b"xyxyx"
    # malformed: declared length never produced
    import pytest
    with pytest.raises(ValueError):
        snappy_uncompress(bytes([200, 1]) + bytes([3 << 2]) + b"abcd")


def test_native_u8_matches_f32_pixels(datum_db):
    """batch_u8 (device-transform ingest) must pick the SAME crop/mirror
    windows as batch under the same seed — only the mean/scale (moved
    on-device) and dtype differ."""
    path, _, _ = datum_db
    b = native.NativeLMDBBatcher(path, crop_size=8, mirror=True, train=True)
    f32, l1 = b.batch(np.arange(16), seed=11)
    u8, l2 = b.batch_u8(np.arange(16), seed=11)
    assert u8.dtype == np.uint8
    np.testing.assert_array_equal(u8.astype(np.float32), f32)
    np.testing.assert_array_equal(l1, l2)
    b.close()


def test_pipeline_device_transform_spec(datum_db):
    """device_transform: uint8 batches + the {mean, scale} spec the step
    must apply; a mean_file config keeps the host path (per-sample crop
    alignment of the full mean cannot be reproduced on device)."""
    path, _, _ = datum_db
    from poseidon_tpu.data.pipeline import BatchPipeline
    from poseidon_tpu.proto.messages import (DataParameter, LayerParameter,
                                             TransformationParameter)

    lp = LayerParameter(
        name="d", type="DATA", top=["data", "label"],
        data_param=DataParameter(source=path, batch_size=8, backend="LMDB"),
        transform_param=TransformationParameter(
            crop_size=8, mirror=True, scale=0.00390625,
            mean_value=[33.0, 34.0, 35.0]))
    pipe = BatchPipeline(lp, "TRAIN", 8, device_transform=True)
    assert pipe.device_transform_spec is not None
    batch = next(pipe)
    assert batch["data"].dtype == np.uint8
    spec = pipe.device_transform_spec
    np.testing.assert_array_equal(spec["mean_values"], [33.0, 34.0, 35.0])
    assert abs(spec["scale"] - 0.00390625) < 1e-12
    pipe.close()

    # host path and device path agree end to end (same seed): the uint8
    # batch put through the spec equals the host-transformed batch
    pipe_h = BatchPipeline(lp, "TRAIN", 8, device_transform=False)
    host = next(pipe_h)
    dev = (batch["data"].astype(np.float32)
           - np.asarray(spec["mean_values"])[None, :, None, None]) \
        * spec["scale"]
    np.testing.assert_allclose(dev, host["data"], rtol=1e-6, atol=1e-6)
    pipe_h.close()


def test_pipeline_device_transform_falls_back_for_float_data(tmp_path):
    """float_data Datums cannot ship as uint8: the init-time probe must
    disable the u8 path (host f32 transform) instead of crashing the
    prefetch worker on the first batch."""
    from poseidon_tpu.data.lmdb_reader import LMDBWriter
    from poseidon_tpu.data.pipeline import BatchPipeline
    from poseidon_tpu.proto.messages import DataParameter, LayerParameter
    from poseidon_tpu.proto.wire import Datum, encode_datum

    path = str(tmp_path / "float_lmdb")
    w = LMDBWriter(path)
    rs = np.random.RandomState(3)
    for i in range(8):
        arr = rs.rand(2, 6, 6).astype(np.float32)
        w.put(f"{i:08d}".encode(),
              encode_datum(Datum(2, 6, 6, b"", label=i % 3,
                                 float_data=arr.ravel().tolist())))
    w.close()

    lp = LayerParameter(
        name="d", type="DATA", top=["data", "label"],
        data_param=DataParameter(source=path, batch_size=4, backend="LMDB"))
    pipe = BatchPipeline(lp, "TRAIN", 4, device_transform=True)
    assert pipe.device_transform_spec is None
    batch = next(pipe)
    assert batch["data"].dtype == np.float32
    pipe.close()


# --------------------------------------------------------------------------- #
# batch memory is recycled, and only when nobody can see it any more
# --------------------------------------------------------------------------- #

def _address(a: np.ndarray) -> int:
    return a.__array_interface__["data"][0]


@pytest.mark.parametrize("method", ["batch", "batch_u8"])
def test_batch_memory_is_reused_once_released(datum_db, method):
    """The next batch is written into the memory of a batch nobody holds
    any more (no fresh pages, no page faults), and holds the same pixels a
    fresh buffer would."""
    path, _, _ = datum_db
    b = native.NativeLMDBBatcher(path, crop_size=8, mirror=True, train=True)
    idx = np.arange(16)
    data, _ = getattr(b, method)(idx, seed=3)
    want, where = data.copy(), _address(data)
    del data
    again, _ = getattr(b, method)(idx, seed=3)
    assert _address(again) == where
    np.testing.assert_array_equal(again, want)


@pytest.mark.parametrize("keep", ["batch", "view", "device_array"])
def test_batch_memory_is_never_reused_under_a_holder(datum_db, keep):
    """A consumer that keeps a batch, a view of one, or a device array made
    from one (the CPU backend aliases host memory; an accelerator's copy
    may still be in flight) keeps its bytes: later batches go elsewhere."""
    import jax

    path, _, _ = datum_db
    b = native.NativeLMDBBatcher(path, crop_size=8, mirror=True, train=True)
    data, _ = b.batch(np.arange(16), seed=1)
    want = data.copy()
    held = {"batch": lambda: data, "view": lambda: data[3, 1],
            "device_array": lambda: jax.device_put(data)}[keep]()
    want = want[3, 1] if keep == "view" else want
    del data
    later = [b.batch(np.arange(16, 32), seed=s)[0] for s in range(4)]
    np.testing.assert_array_equal(np.asarray(held), want)
    assert len({_address(a) for a in later}) == 4
    # ... and goes back on the free list with its last holder (a device
    # array lets go when the runtime is done with it, not at `del`)
    del held
    if keep != "device_array":
        assert len(b._buffers._free) == 1
