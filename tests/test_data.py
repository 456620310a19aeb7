
import numpy as np

from poseidon_tpu.data.lmdb_reader import LMDBReader, LMDBWriter
from poseidon_tpu.data.sources import ImageListSource, SyntheticSource
from poseidon_tpu.data.transformer import DataTransformer
from poseidon_tpu.data.workload import Shard, contiguous_range, shard_indices
from poseidon_tpu.proto.messages import TransformationParameter
from poseidon_tpu.proto.wire import (Datum, decode_datum, encode_blob,
                                     decode_blob, encode_datum)


def test_datum_wire_roundtrip():
    arr = np.arange(2 * 3 * 4, dtype=np.uint8).reshape(2, 3, 4)
    d = Datum(channels=2, height=3, width=4, data=arr.tobytes(), label=7)
    d2 = decode_datum(encode_datum(d))
    assert d2.label == 7
    np.testing.assert_array_equal(d2.to_array(),
                                  arr.astype(np.float32))
    # float_data variant
    f = Datum(channels=3, height=1, width=1,
              float_data=np.asarray([1.5, -2.0, 0.25], np.float32))
    f2 = decode_datum(encode_datum(f))
    np.testing.assert_allclose(f2.to_array().ravel(), [1.5, -2.0, 0.25])


def test_blob_wire_roundtrip():
    arr = np.random.RandomState(0).randn(2, 3, 4, 5).astype(np.float32)
    b = decode_blob(encode_blob(arr))
    assert b.shape == (2, 3, 4, 5)
    np.testing.assert_allclose(b.to_array(), arr)


def test_lmdb_write_read_roundtrip(tmp_path):
    path = str(tmp_path / "db")
    w = LMDBWriter(path)
    values = {}
    rs = np.random.RandomState(0)
    for i in range(300):  # enough entries to force multiple leaves + branch
        key = f"{i:08d}".encode()
        val = rs.bytes(rs.randint(10, 200))
        values[key] = val
        w.put(key, val)
    # one oversized value to exercise overflow pages
    big_key = b"zz_big"
    big_val = rs.bytes(20000)
    values[big_key] = big_val
    w.put(big_key, big_val)
    w.close()

    r = LMDBReader(path)
    assert len(r) == 301
    seen = dict(iter(r))
    assert seen == values
    # keys come back sorted
    assert list(seen) == sorted(values)
    # random access
    assert r.value_at(0) == values[sorted(values)[0]]
    r.close()


def test_lmdb_datum_pipeline(tmp_path):
    path = str(tmp_path / "datumdb")
    w = LMDBWriter(path)
    rs = np.random.RandomState(1)
    for i in range(20):
        arr = rs.randint(0, 255, size=(3, 8, 8)).astype(np.uint8)
        d = Datum(3, 8, 8, arr.tobytes(), label=i % 10)
        w.put(f"{i:08d}".encode(), encode_datum(d))
    w.close()

    from poseidon_tpu.data.sources import LMDBSource
    src = LMDBSource(path)
    assert len(src) == 20
    arr, label = src.read(3)
    assert arr.shape == (3, 8, 8)
    assert label == 3


def test_transformer_center_crop_and_mean_values():
    tp = TransformationParameter(crop_size=2, mean_value=[1.0, 2.0, 3.0],
                                 scale=0.5)
    t = DataTransformer(tp, "TEST")
    x = np.arange(3 * 4 * 4, dtype=np.float32).reshape(1, 3, 4, 4)
    y = t(x)
    assert y.shape == (1, 3, 2, 2)
    # center crop offset (4-2)//2 = 1
    want = (x[0, :, 1:3, 1:3]
            - np.asarray([1, 2, 3], np.float32)[:, None, None]) * 0.5
    np.testing.assert_allclose(y[0], want)


def test_transformer_mean_file_indexed_at_crop(tmp_path):
    mean = np.random.RandomState(0).rand(1, 3, 4, 4).astype(np.float32)
    mean_path = str(tmp_path / "mean.binaryproto")
    with open(mean_path, "wb") as f:
        f.write(encode_blob(mean))
    tp = TransformationParameter(crop_size=2, mean_file=mean_path)
    t = DataTransformer(tp, "TEST")
    x = np.ones((1, 3, 4, 4), np.float32) * 10
    y = t(x)
    want = 10 - mean[0][:, 1:3, 1:3]
    np.testing.assert_allclose(y[0], want, rtol=1e-6)


def test_transformer_random_crop_mirror_train():
    tp = TransformationParameter(crop_size=3, mirror=True)
    t = DataTransformer(tp, "TRAIN", seed=0)
    x = np.random.RandomState(0).rand(64, 1, 5, 5).astype(np.float32)
    y = t(x)
    assert y.shape == (64, 1, 3, 3)
    # every output window must be an actual (possibly mirrored) crop
    found = 0
    for i in range(8):
        ok = False
        for ho in range(3):
            for wo in range(3):
                win = x[i, 0, ho:ho + 3, wo:wo + 3]
                if np.allclose(y[i, 0], win) or \
                        np.allclose(y[i, 0], win[:, ::-1]):
                    ok = True
        found += ok
    assert found == 8


def test_workload_sharding():
    n = 103
    ranges = [contiguous_range(n, Shard(i, 8)) for i in range(8)]
    assert ranges[0][0] == 0 and ranges[-1][1] == n
    sizes = [e - b for b, e in ranges]
    assert sum(sizes) == n and max(sizes) - min(sizes) <= 1
    # epoch permutation keeps shards disjoint and covering
    all_idx = np.concatenate(
        [shard_indices(n, Shard(i, 8), epoch=4) for i in range(8)])
    assert sorted(all_idx.tolist()) == list(range(n))


def test_batch_pipeline_memory_source():
    from poseidon_tpu.data.pipeline import BatchPipeline
    from poseidon_tpu.proto.messages import (LayerParameter,
                                             MemoryDataParameter)
    rs = np.random.RandomState(0)
    data = rs.rand(50, 1, 6, 6).astype(np.float32)
    labels = np.arange(50) % 3
    lp = LayerParameter(
        name="mem", type="MEMORY_DATA", top=["data", "label"],
        memory_data_param=MemoryDataParameter(batch_size=10, channels=1,
                                              height=6, width=6))
    pipe = BatchPipeline(lp, "TRAIN", 10,
                         memory_data={"data": data, "label": labels})
    batches = [next(pipe) for _ in range(5)]  # exactly one epoch of 50
    assert batches[0]["data"].shape == (10, 1, 6, 6)
    assert batches[0]["label"].shape == (10,)
    # one epoch covers every record exactly once, shuffled
    epoch_labels = np.concatenate([b["label"] for b in batches])
    assert sorted(epoch_labels.tolist()) == sorted(labels.tolist())
    assert not np.array_equal(epoch_labels, labels)  # shuffle happened
    pipe.close()


def test_image_list_source(tmp_path):
    from PIL import Image
    rs = np.random.RandomState(0)
    listfile = tmp_path / "list.txt"
    lines = []
    for i in range(4):
        img = Image.fromarray(
            rs.randint(0, 255, size=(10, 12, 3)).astype(np.uint8))
        p = tmp_path / f"img{i}.png"
        img.save(p)
        lines.append(f"{p} {i}")
    listfile.write_text("\n".join(lines))
    src = ImageListSource(str(listfile), new_height=8, new_width=8)
    assert len(src) == 4
    arr, label = src.read(2)
    assert arr.shape == (3, 8, 8)
    assert label == 2


def test_synthetic_source_learnable():
    src = SyntheticSource((1, 4, 4), num_classes=3, size=30)
    a0, l0 = src.read(0)
    a3, l3 = src.read(3)
    assert l0 == 0 and l3 == 0
    assert a0.shape == (1, 4, 4)
    # same class, different noise
    assert not np.allclose(a0, a3)


def test_window_data_source(tmp_path):
    from PIL import Image
    from poseidon_tpu.data.window import WindowDataSource
    from poseidon_tpu.proto.messages import (LayerParameter,
                                             TransformationParameter,
                                             WindowDataParameter)
    rs = np.random.RandomState(0)
    img_paths = []
    for i in range(2):
        img = Image.fromarray(rs.randint(0, 255, (40, 40, 3)).astype(np.uint8))
        p = tmp_path / f"w{i}.png"
        img.save(p)
        img_paths.append(str(p))
    wf = tmp_path / "windows.txt"
    wf.write_text(f"""# 0
{img_paths[0]}
3 40 40
3
1 0.9 5 5 20 20
2 0.7 10 10 30 30
0 0.1 0 0 10 10
# 1
{img_paths[1]}
3 40 40
2
1 0.8 0 0 15 15
0 0.05 20 20 39 39
""")
    lp = LayerParameter(
        name="wd", type="WINDOW_DATA", top=["data", "label"],
        window_data_param=WindowDataParameter(
            source=str(wf), batch_size=8, crop_size=12, fg_threshold=0.5,
            bg_threshold=0.3, fg_fraction=0.5, context_pad=2),
        transform_param=TransformationParameter(crop_size=12))
    src = WindowDataSource(lp, "TRAIN")
    assert len(src.fg) == 3 and len(src.bg) == 2
    data, labels = src.batch(8)
    assert data.shape == (8, 3, 12, 12)
    assert set(labels[:4]) <= {1, 2}   # fg half
    assert set(labels[4:]) == {0}      # bg half

    from poseidon_tpu.data.pipeline import BatchPipeline
    pipe = BatchPipeline(lp, "TRAIN", 8)
    b = next(pipe)
    assert b["data"].shape == (8, 3, 12, 12)
    pipe.close()


def test_libsvm_parser(tmp_path):
    from poseidon_tpu.data.libsvm import read_libsvm
    f = tmp_path / "data.svm"
    f.write_text("""1 1:0.5 3:1.5
-1 2:2.0 # comment
1 1:1.0 4:0.25
""")
    feats, labels = read_libsvm(str(f))
    np.testing.assert_allclose(labels, [1, -1, 1])
    dense = feats.to_dense()
    assert dense.shape == (3, 4)
    np.testing.assert_allclose(dense[0], [0.5, 0, 1.5, 0])
    np.testing.assert_allclose(dense[1], [0, 2.0, 0, 0])


def test_token_source_through_batch_pipeline(tmp_path):
    """An HDF5 file of 2-D integer ``data`` and ``label`` is a token file:
    each record is seq_len ids and seq_len targets, int32 end to end, read
    by the same BatchPipeline and build_phase_pipelines as the images."""
    import h5py
    from poseidon_tpu.data.pipeline import build_phase_pipelines
    from poseidon_tpu.data.sources import HDF5Source
    from poseidon_tpu.proto.messages import load_net_from_string
    ids = np.arange(6 * 16, dtype=np.int64).reshape(6, 16) % 50304
    with h5py.File(tmp_path / "tok.h5", "w") as h:
        h["data"] = ids
        h["label"] = np.roll(ids, -1, axis=1)
    (tmp_path / "tok.txt").write_text(str(tmp_path / "tok.h5") + "\n")
    src = HDF5Source(str(tmp_path / "tok.txt"))
    assert src.tokens and len(src) == 6 and src.record_shape == (16,)
    net = load_net_from_string(f"""
        layers {{ name: "t" type: HDF5_DATA top: "tokens" top: "targets"
                 hdf5_data_param {{ source: "{tmp_path / 'tok.txt'}"
                                    batch_size: 2 }} }}""")
    pipes, shapes = build_phase_pipelines(net, "TEST", batch_multiplier=1)
    assert shapes == {"tokens": (2, 16), "targets": (2, 16)}
    seen = []
    for _ in range(3):
        b = next(pipes[0])
        assert b["tokens"].dtype == b["targets"].dtype == np.int32
        assert b["tokens"].shape == b["targets"].shape == (2, 16)
        np.testing.assert_array_equal(b["targets"],
                                      np.roll(b["tokens"], -1, axis=1))
        seen.extend(b["tokens"][:, 0].tolist())
    assert sorted(seen) == ids[:, 0].tolist()   # one epoch, every record
    pipes[0].close()
