"""Host data-plane throughput bench: can ingest feed the chip?

The native batcher (native/poseidon_dataplane.cc) exists to play the
reference's BasePrefetchingDataLayer role
(/root/reference/src/caffe/layers/base_data_layer.cpp:73-103): decode +
augment batches on host threads so the accelerator never waits. This script
measures that pipeline's images/s on ILSVRC12-shaped Datums (3x256x256
uint8, crop 227, mirror, per-pixel mean — the AlexNet training transform)
and compares it against the training step rate, the way the reference's
prefetch thread is judged by whether Forward ever blocks on it.

Prints ONE JSON line:
  {"metric": "dataplane_images_per_sec", "value": N, "unit": "images/s",
   "python_path_images_per_sec": N, "step_rate_images_per_sec": N|null,
   "ingest_over_consume": N|null, ...}

``step_rate_images_per_sec`` is read from BENCH_last_good.json (the measured
TPU step rate) when available; the headline ratio ingest_over_consume >= 2.0
means the data plane sustains double the chip's appetite (the margin the
round-2 verdict asks for).

Usage: python scripts/bench_dataplane.py [--records 256] [--batches 8]
       [--batch 256] (no TPU needed; jax is not imported)
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_db(path: str, n_records: int) -> None:
    from poseidon_tpu.data.lmdb_reader import LMDBWriter
    from poseidon_tpu.proto.wire import Datum, encode_datum
    rs = np.random.RandomState(0)
    w = LMDBWriter(path)
    for i in range(n_records):
        img = rs.randint(0, 256, size=(3, 256, 256), dtype=np.uint8)
        d = Datum(channels=3, height=256, width=256,
                  data=img.tobytes(), label=int(i % 1000))
        w.put(f"{i:08d}".encode(), encode_datum(d))
    w.close()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=256)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--threads", type=int, default=0)
    args = ap.parse_args()

    from poseidon_tpu.data import native

    tmp = tempfile.mkdtemp(prefix="dataplane_bench_")
    db = os.path.join(tmp, "ilsvrc_shaped_lmdb")
    payload: dict = {"metric": "dataplane_images_per_sec", "value": 0.0,
                     "unit": "images/s"}
    try:
        t0 = time.perf_counter()
        build_db(db, args.records)
        payload["db_build_s"] = round(time.perf_counter() - t0, 2)

        mean = np.full((3, 256, 256), 120.0, np.float32)
        rs = np.random.RandomState(1)

        if native.available():
            b = native.NativeLMDBBatcher(
                db, crop_size=227, mirror=True, train=True,
                scale=1.0, mean=mean, n_threads=args.threads)
            idx = rs.randint(0, args.records, size=(args.batch,))
            b.batch(idx, seed=0)  # warm the page cache + thread pool
            t0 = time.perf_counter()
            for i in range(args.batches):
                idx = rs.randint(0, args.records, size=(args.batch,))
                data, labels = b.batch(idx, seed=i)
            dt = time.perf_counter() - t0
            native_ips = args.batches * args.batch / dt
            payload["value"] = round(native_ips, 1)
            payload["n_threads"] = b.n_threads
            # per-core scaling context: this sandbox may have far fewer
            # cores than a real TPU-VM host (which has 96-240)
            payload["host_cores"] = os.cpu_count()
            payload["images_per_sec_per_core"] = round(
                native_ips / max(1, b.n_threads), 1)
            assert data.shape == (args.batch, 3, 227, 227)

            # uint8 device-transform path (pipeline.device_transform): the
            # host only decodes + crops + mirrors; mean/scale ride the
            # compiled step, and the transfer is quarter-width
            b.batch_u8(idx, seed=0)
            t0 = time.perf_counter()
            for i in range(args.batches):
                idx = rs.randint(0, args.records, size=(args.batch,))
                u8, _ = b.batch_u8(idx, seed=i)
            dt = time.perf_counter() - t0
            u8_ips = args.batches * args.batch / dt
            payload["u8_images_per_sec"] = round(u8_ips, 1)
            payload["u8_speedup_vs_f32_host"] = round(
                u8_ips / native_ips, 2)
            payload["u8_bytes_per_image"] = int(u8[0].nbytes)
            payload["f32_bytes_per_image"] = int(data[0].nbytes)
            b.close()
        else:
            payload["error"] = "native data plane unavailable"

        # pure-Python comparison path (the fallback the native plane exists
        # to beat): LMDB read + Datum decode + DataTransformer per record
        from poseidon_tpu.data.lmdb_reader import LMDBReader
        from poseidon_tpu.data.transformer import DataTransformer
        from poseidon_tpu.proto.messages import TransformationParameter
        from poseidon_tpu.proto.wire import decode_datum
        r = LMDBReader(db)
        tp = TransformationParameter(crop_size=227, mirror=True, scale=1.0)
        tr = DataTransformer(tp, phase="TRAIN", mean=mean)
        n_py = min(args.batch, args.records)
        t0 = time.perf_counter()
        rng = np.random.RandomState(2)
        imgs = []
        for i in range(n_py):
            d = decode_datum(r.value_at(int(rng.randint(0, args.records))))
            imgs.append(np.frombuffer(d.data, np.uint8)
                        .reshape(3, 256, 256).astype(np.float32))
        tr(np.stack(imgs))
        py_dt = time.perf_counter() - t0
        payload["python_path_images_per_sec"] = round(n_py / py_dt, 1)
        if payload["value"]:
            payload["native_speedup"] = round(
                payload["value"] / payload["python_path_images_per_sec"], 2)

        # compare against the measured chip appetite when a bench exists
        step_rate = None
        lg = os.path.join(REPO, "BENCH_last_good.json")
        if os.path.exists(lg):
            try:
                with open(lg) as f:
                    step_rate = float(json.load(f)["value"])
            except Exception:  # noqa: BLE001
                pass
        payload["step_rate_images_per_sec"] = step_rate
        payload["ingest_over_consume"] = (
            round(payload["value"] / step_rate, 2) if step_rate else None)
        if step_rate and payload.get("u8_images_per_sec"):
            payload["u8_ingest_over_consume"] = round(
                payload["u8_images_per_sec"] / step_rate, 2)
    except Exception as e:  # noqa: BLE001
        payload["error"] = f"{type(e).__name__}: {e}"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(payload), flush=True)
    if "error" in payload:
        sys.exit(1)


if __name__ == "__main__":
    main()
