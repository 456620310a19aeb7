"""Batch pipeline: source -> transform -> background prefetch -> device.

The counterpart of ``BasePrefetchingDataLayer`` + ``InternalThread``
(``src/caffe/layers/base_data_layer.cpp:73-103``): a daemon thread keeps a
bounded queue of ready batches (transform applied, numpy, pinned layout) while
the TPU trains on the current one; ``__next__`` hands back host arrays the
trainer device_puts with the batch sharding.

``build_source`` maps a data-layer ``LayerParameter`` to a Source with the
reference's backend selection (data_layer.cpp, layer catalog §2.1) and the
``shared_file_system`` `_k` suffix sharding.
"""

from __future__ import annotations

import queue
import threading
from typing import Dict, Iterator, Optional

import numpy as np

from ..proto.messages import LayerParameter, TransformationParameter
from ..runtime.spans import recorder as _spans
from .sources import (HDF5Source, ImageListSource, LMDBSource, LevelDBSource,
                      MemorySource, Source)
from .transformer import DataTransformer
from .workload import Shard, shard_indices, sharded_source_path


def _effective_transform(lp: LayerParameter) -> TransformationParameter:
    """Merge the deprecated in-layer fields (scale/mean_file/crop/mirror on
    data_param etc.) into a TransformationParameter, preferring the modern
    transform_param when set (upgrade_proto.cpp behavior)."""
    tp = lp.transform_param
    legacy = None
    t = lp.canonical_type()
    if t == "DATA":
        legacy = lp.data_param
    elif t == "IMAGE_DATA":
        legacy = lp.image_data_param
    elif t == "WINDOW_DATA":
        legacy = lp.window_data_param
    if legacy is not None:
        merged = TransformationParameter(
            scale=tp.scale if tp.scale != 1.0 else legacy.scale,
            mirror=tp.mirror or legacy.mirror,
            crop_size=tp.crop_size or legacy.crop_size,
            mean_file=tp.mean_file or legacy.mean_file,
            mean_value=list(tp.mean_value),
        )
        return merged
    return tp


def build_source(lp: LayerParameter, shard: Shard,
                 memory_data: Optional[Dict[str, np.ndarray]] = None) -> Source:
    t = lp.canonical_type()
    if t == "DATA":
        dp = lp.data_param
        path = sharded_source_path(dp.source, shard.index,
                                   dp.shared_file_system)
        if dp.backend == "LMDB":
            return LMDBSource(path)
        # LEVELDB (the default). Tolerate a converted LMDB at the same path.
        try:
            return LevelDBSource(path)
        except Exception:
            return LMDBSource(path)
    if t == "IMAGE_DATA":
        ip = lp.image_data_param
        path = sharded_source_path(ip.source, shard.index,
                                   ip.shared_file_system)
        return ImageListSource(path, ip.root_folder, ip.new_height,
                               ip.new_width, ip.shuffle)
    if t == "HDF5_DATA":
        return HDF5Source(lp.hdf5_data_param.source)
    if t == "MEMORY_DATA":
        if memory_data is None:
            raise ValueError(
                f"layer {lp.name!r}: MEMORY_DATA requires arrays passed via "
                f"memory_data={{'data': ..., 'label': ...}}")
        return MemorySource(memory_data["data"], memory_data["label"])
    raise ValueError(f"layer {lp.name!r}: {t} is not a batch source")


def layer_batch_size(lp: LayerParameter) -> int:
    t = lp.canonical_type()
    return {
        "DATA": lp.data_param.batch_size,
        "IMAGE_DATA": lp.image_data_param.batch_size,
        "HDF5_DATA": lp.hdf5_data_param.batch_size,
        "MEMORY_DATA": lp.memory_data_param.batch_size,
        "WINDOW_DATA": lp.window_data_param.batch_size,
    }[t]


# While the recorder is enabled the prefetcher waits for every LAND_EVERY-th
# batch to land on the device (``producer_h2d_land``), and for the batch
# before it, so that the sampled copy has the link to itself. Every batch
# was tried first: in ``alexnet.lmdb`` (316.6 MB a batch) one transfer alone
# takes 32 ms, the step 29.9, so copies that may not overlap made the cell
# 28% slower under tracing (PERF.md section 6, PR 51).
LAND_EVERY = 16


def _batch_args(batch_no: int) -> Optional[Dict]:
    """Span args of a producer span — built only while the recorder is on,
    so the producer loops of an untraced run allocate nothing for it."""
    return {"batch": batch_no} if _spans.enabled else None


class BatchPipeline:
    """Iterates {top_name: np.ndarray} batches forever (epoch wraparound),
    prefetching `prefetch` batches ahead on a daemon thread."""

    def __init__(
        self,
        lp: LayerParameter,
        phase: str,
        batch_size: int,
        shard: Shard = Shard(0, 1),
        prefetch: int = 3,
        seed: int = 0,
        shuffle: Optional[bool] = None,
        memory_data: Optional[Dict[str, np.ndarray]] = None,
        use_native: bool = True,
        device_transform: bool = False,
    ):
        self.lp = lp
        self.phase = phase
        self.batch_size = batch_size
        self.shard = shard
        self.seed = seed
        self.shuffle = (phase == "TRAIN") if shuffle is None else shuffle
        self.tops = list(lp.top)
        self.label_shape: tuple = ()    # per record; (seq_len,) for tokens
        # device_transform: ship uint8 crops and let the compiled step do
        # (x - mean) * scale on the accelerator — 4x fewer host->device
        # bytes and no per-pixel float math on the host (the TPU-native
        # split of DataTransformer's work). Engaged only when the native
        # batcher supports it; ``device_transform_spec`` is then the
        # {mean, scale} the training side must apply.
        self.device_transform_spec: Optional[Dict] = None
        self._want_device_transform = device_transform

        self.window = None
        if lp.canonical_type() == "WINDOW_DATA":
            from .window import WindowDataSource
            self.window = WindowDataSource(
                lp, phase, seed=seed * shard.count + shard.index)
            self.native = None
            self.source = None
            self._n_records = len(self.window.fg) + len(self.window.bg)
            self.data_shape = (batch_size,) + self.window.record_shape
            self._queue = queue.Queue(maxsize=prefetch)
            self._thread = threading.Thread(target=self._worker,
                                            name="reader", daemon=True)
            self._stop = threading.Event()
            self._thread.start()
            return
        self.native = self._try_native(lp, phase, shard) if use_native else None
        self._u8 = False
        if self.native is not None:
            self.source = None
            self._n_records = len(self.native)
            self.data_shape = (batch_size,) + self.native.out_shape
            tp = _effective_transform(lp)
            # exactness constraint: a full mean_file is subtracted at the
            # per-sample SOURCE crop position (data_transformer.cpp indexes
            # the mean by h_off/w_off), which the device cannot see — only
            # mean_value/no-mean configs move on-device
            if (self._want_device_transform and not tp.mean_file
                    and self._n_records):
                # probe a spread of records: float_data-backed Datums cannot
                # ship as uint8 (rc=-4), and a MIXED byte/float DB detected
                # here gets the host f32 path for the whole pipeline — the
                # only moment the wire contract can still change (once the
                # step compiles against the uint8 spec, a mid-epoch float
                # record can only be re-quantized, lossily). IndexError
                # covers a DB that vanished between len() and here; the
                # empty-DB case is excluded by _n_records above.
                n = self._n_records
                probe = np.unique(np.linspace(0, n - 1, num=min(n, 8),
                                              dtype=np.int64))
                try:
                    self.native.batch_u8(probe)
                    self._u8 = True
                except (IOError, IndexError):
                    self._u8 = False
            if self._u8:
                mv = (np.asarray(tp.mean_value, np.float32)
                      if tp.mean_value else None)
                if mv is not None and mv.size == 1:
                    mv = np.repeat(mv, self.native.out_shape[0])
                self.device_transform_spec = {
                    "mean_values": mv, "scale": float(tp.scale)}
        else:
            self.source = build_source(lp, shard, memory_data)
            self._n_records = len(self.source)
            if self.source.tokens:
                # sequences of ids: no image transform, targets per position
                self.transformer = None
                self.data_shape = (batch_size,) + self.source.record_shape
                self.label_shape = self.data_shape[1:]
            else:
                self.transformer = DataTransformer(
                    _effective_transform(lp), phase, seed=seed)
                c, h, w = self.source.record_shape
                self.data_shape = (batch_size,) + \
                    self.transformer.output_shape(c, h, w)
        self._queue: queue.Queue = queue.Queue(maxsize=prefetch)
        self._thread = threading.Thread(target=self._worker, name="reader",
                                        daemon=True)
        self._stop = threading.Event()
        self._thread.start()

    def _try_native(self, lp: LayerParameter, phase: str, shard: Shard):
        """C++ fast path for LMDB-backed DATA layers (native/...dataplane.cc);
        a source the native reader cannot open takes the Python source.
        Which reader runs is logged either way — the Python reader is
        ~50x slower and a silent fall onto it reads as a slow chip."""
        if lp.canonical_type() != "DATA":
            return None
        from ..runtime.metrics import log
        from .native import NativeLMDBBatcher, available
        dp = lp.data_param
        what = f"[data] {lp.name} ({phase}, {dp.source})"
        if not available():
            log(f"{what}: Python reader (native data plane unavailable)")
            return None
        try:
            path = sharded_source_path(dp.source, shard.index,
                                       dp.shared_file_system)
            tp = _effective_transform(lp)
            mean = None
            if tp.mean_file:
                from ..proto.wire import read_blob_file
                mean = read_blob_file(tp.mean_file)[0]
            native = NativeLMDBBatcher(
                path, crop_size=tp.crop_size, mirror=tp.mirror,
                train=(phase == "TRAIN"), scale=tp.scale, mean=mean,
                mean_values=np.asarray(tp.mean_value, np.float32)
                if tp.mean_value else None)
        except (OSError, ValueError) as e:
            log(f"{what}: Python reader (native reader refused the "
                f"source: {e})")
            return None
        log(f"{what}: native C++ reader, {native.n_threads} threads")
        return native

    # ------------------------------------------------------------------ #
    def _index_stream(self) -> Iterator[int]:
        epoch = 0
        while True:
            idx = shard_indices(self._n_records, self.shard, epoch,
                                self.shuffle, self.seed)
            if len(idx) == 0:
                raise RuntimeError("shard received zero records")
            yield from idx
            epoch += 1

    def _put(self, batch: Dict[str, np.ndarray], batch_no: int) -> None:
        """Hand one batch to the consumer. ``producer_queue_full`` spans
        only the time this thread is blocked on a full queue: its share of
        a window is how far the reader is ahead of the step, and a reader
        that never records one is the bottleneck."""
        try:
            self._queue.put_nowait(batch)
        except queue.Full:
            with _spans.span("producer_queue_full", "input",
                             _batch_args(batch_no)):
                self._queue.put(batch)

    def _worker(self):
        # producer_read = the making of one batch (index draw + read +
        # transform), numbered as the consumer will dequeue it
        batch_no = 0
        if self.window is not None:
            try:
                while not self._stop.is_set():
                    with _spans.span("producer_read", "input",
                                     _batch_args(batch_no)):
                        data, labels = self.window.batch(self.batch_size)
                    batch = {self.tops[0]: data}
                    if len(self.tops) > 1:
                        batch[self.tops[1]] = labels
                    self._put(batch, batch_no)
                    batch_no += 1
            except Exception as e:
                self._queue.put(e)
            return
        stream = self._index_stream()
        self._warned_mixed = False
        try:
            while not self._stop.is_set():
                with _spans.span("producer_read", "input",
                                 _batch_args(batch_no)):
                    data, labels = self._read_batch(stream, batch_no)
                batch = {self.tops[0]: data}
                if len(self.tops) > 1:
                    batch[self.tops[1]] = labels
                self._put(batch, batch_no)
                batch_no += 1
        except Exception as e:  # surface worker death to the consumer
            self._queue.put(e)

    def _read_batch(self, stream: Iterator[int], batch_no: int):
        """One batch's ``(data, labels)``: the next ``batch_size`` indices
        of the shard's stream through the native batcher, or read record
        by record and transformed in Python."""
        idx = np.fromiter((next(stream) for _ in range(self.batch_size)),
                          np.int64, count=self.batch_size)
        if self.native is None and self.source.tokens:
            return self.source.data_cat[idx], self.source.labels_cat[idx]
        if self.native is None:
            raw = np.empty((self.batch_size,) + self.source.record_shape,
                           np.float32)
            labels = np.empty((self.batch_size,), np.int32)
            for i, j in enumerate(idx):
                arr, label = self.source.read(int(j))
                raw[i] = arr
                labels[i] = label
            return self.transformer(raw), labels
        seed = self.seed * 1_000_003 + batch_no
        if not self._u8:
            return self.native.batch(idx, seed=seed)
        try:
            return self.native.batch_u8(idx, seed=seed)
        except IOError:
            # mixed byte/float DB: the init probe saw record 0 byte-backed,
            # but THIS batch hit a float_data Datum (rc=-4). Keep the uint8
            # wire contract by undoing the host transform's
            # (x - mean) * scale (same seed -> same crop/mirror), instead
            # of killing the prefetch worker mid-epoch.
            data, labels = self.native.batch(idx, seed=seed)
            spec = self.device_transform_spec or {}
            raw = data / (spec.get("scale") or 1.0)
            mv = spec.get("mean_values")
            if mv is not None:
                raw = raw + mv.reshape(1, -1, 1, 1)
            data = np.clip(np.rint(raw), 0, 255).astype(np.uint8)
            if not self._warned_mixed:
                self._warned_mixed = True
                import sys
                print("WARNING: mixed byte/float LMDB under "
                      "--device_transform; float_data records are "
                      "re-quantized to uint8 per batch (lossy for values "
                      "outside [0,255])", file=sys.stderr, flush=True)
            return data, labels

    def __iter__(self):
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        item = self._queue.get()
        if isinstance(item, Exception):
            raise item
        return item

    def close(self):
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass


def place_batch(value: np.ndarray, sharding):
    """Host array -> device array under the trainer's batch sharding: THE
    placement rule, shared by the engine's inline feed, the device
    prefetcher, and the tools path. Multi-process assembles the global
    array from this process's local rows; ``sharding=None`` is a plain
    default-device put. jax is imported lazily so this module stays
    importable from jax-free socket-tier processes."""
    import jax
    if sharding is None:
        return jax.device_put(value)
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sharding, value)
    return jax.device_put(value, sharding)


class DevicePrefetcher:
    """Device-side half of the input pipeline: a background stage that
    ``jax.device_put``s the next ``depth`` host batches with the trainer's
    batch sharding while the current step runs, so the train thread only
    ever dequeues device-RESIDENT arrays (the host->device copy is off the
    critical path, like the reference's prefetch thread hides decode).

    Wraps a list of :class:`BatchPipeline`-like iterators (their per-top
    dicts are merged into one batch, the ``Engine._next_batch`` contract)
    and owns one daemon thread. Exceptions from the underlying pipelines
    (a dead prefetch worker, a vanished DB) propagate to the consumer on
    ``__next__`` instead of wedging the queue. jax is imported lazily so
    this module stays importable from jax-free socket-tier processes.

    ``passthrough`` resolves per-backend by default (the conv_layout=auto
    pattern): on the CPU backend ``device_put`` moves no bytes over any
    link, so a background put thread is pure core oversubscription —
    measured ~10% per-step LOSS on a 2-core host — and the stage degrades
    to inline assembly with the same contract (sharded placement, sticky
    error surfacing). Accelerator backends get the real thread.
    """

    def __init__(self, pipes, sharding, depth: int = 2,
                 passthrough: Optional[bool] = None):
        self.pipes = list(pipes)
        self.sharding = sharding
        self.depth = max(1, int(depth))
        self.passthrough = (self._auto_passthrough() if passthrough is None
                            else bool(passthrough))
        self._error: Optional[Exception] = None
        self._thread = None
        self._taken = 0    # passthrough arm: batches handed out so far
        if not self.passthrough:
            self._queue: queue.Queue = queue.Queue(maxsize=self.depth)
            self._stop = threading.Event()
            self._thread = threading.Thread(target=self._worker,
                                            name="prefetcher", daemon=True)
            self._thread.start()

    @staticmethod
    def _auto_passthrough() -> bool:
        import jax
        return jax.default_backend() == "cpu"

    def _place_next(self, batch_no: int) -> Dict:
        """Dequeue one host batch from every pipeline and place it.
        ``producer_h2d`` spans the ``place_batch`` calls alone: what it
        takes this thread to hand the bytes to the runtime. ``device_put``
        returns before the copy has landed on the device: while the
        recorder is enabled (and only then) this thread also waits, for
        every ``LAND_EVERY``-th batch, until the batch's arrays are ready,
        under ``producer_h2d_land``: the two spans together are what ONE
        batch's copy takes with the link to itself, because the batch
        before the sampled one is waited for too (under no span: it lands
        behind its own predecessor) before it is handed on. Either wait
        holds the next batch's copy back on this thread, which is why it
        is a sample; an untraced run waits for nothing here."""
        host: Dict[str, np.ndarray] = {}
        for pipe in self.pipes:
            host.update(next(pipe))
        args = _batch_args(batch_no)
        if args is not None:
            args["bytes"] = sum(v.nbytes for v in host.values())
        with _spans.span("producer_h2d", "input", args):
            batch = {k: place_batch(v, self.sharding)
                     for k, v in host.items()}
        turn = batch_no % LAND_EVERY if args is not None else None
        if turn == LAND_EVERY - 1:          # clear the link for the sample
            import jax
            jax.block_until_ready(batch)
        elif turn == 0:
            import jax
            with _spans.span("producer_h2d_land", "input", args):
                jax.block_until_ready(batch)
        return batch

    def _worker(self):
        batch_no = -1
        try:
            while not self._stop.is_set():
                batch_no += 1
                batch = self._place_next(batch_no)
                try:
                    self._queue.put_nowait(batch)
                    continue
                except queue.Full:
                    pass
                # bounded put that still honors close(): a full queue must
                # not pin this thread forever after the consumer left
                with _spans.span("producer_queue_full", "input",
                                 _batch_args(batch_no)):
                    while not self._stop.is_set():
                        try:
                            self._queue.put(batch, timeout=0.1)
                            break
                        except queue.Full:
                            continue
        except Exception as e:  # surface pipeline death to the consumer
            self._error = e  # sticky BEFORE the sentinel: set-then-put
            self._queue.put(e)

    def __iter__(self):
        return self

    def __next__(self):
        if self.passthrough:
            if self._error is not None:
                raise self._error
            try:
                batch = self._place_next(self._taken)
                self._taken += 1
                return batch
            except Exception as e:
                self._error = e  # same sticky-death contract as threaded
                raise
        # drain queued batches first (the FIFO puts the death sentinel
        # after every good batch); then a dead worker is dead for good —
        # every subsequent dequeue re-raises instead of blocking forever
        # on the empty queue of a thread that already exited (a retried
        # train() fails loudly)
        try:
            item = self._queue.get_nowait()
        except queue.Empty:
            if self._error is not None:
                raise self._error
            item = self._queue.get()
        if isinstance(item, Exception):
            self._error = item
            raise item
        return item

    def close(self):
        if self._thread is None:
            return
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=2.0)


def build_phase_pipelines(net_param, phase: str, batch_multiplier: int,
                          shard: Shard = Shard(0, 1),
                          memory_data: Optional[Dict[str, np.ndarray]] = None,
                          seed: int = 0, device_transform: bool = False):
    """Build a BatchPipeline per data layer of `net_param` at `phase`.

    Returns (pipelines, source_shapes) where source_shapes carry the
    PER-DEVICE batch (the prototxt batch_size) and each pipeline yields
    batch_size * batch_multiplier rows (the caller's per-host batch).
    Shared by Engine, `test`, and `extract_features` so batch semantics stay
    in one place.
    """
    from ..core.layers import DATA_SOURCE_TYPES
    from ..core.net import filter_net
    from ..proto.messages import NetState

    pipes = []
    shapes: Dict[str, tuple] = {}
    for lp in filter_net(net_param, NetState(phase=phase)):
        if lp.canonical_type() not in DATA_SOURCE_TYPES:
            continue
        per_dev = layer_batch_size(lp)
        if per_dev <= 0:
            raise ValueError(f"layer {lp.name!r}: batch_size must be set")
        pipe = BatchPipeline(lp, phase, per_dev * batch_multiplier,
                             shard=shard, memory_data=memory_data, seed=seed,
                             device_transform=device_transform)
        pipes.append(pipe)
        shapes[lp.top[0]] = (per_dev,) + tuple(pipe.data_shape[1:])
        if len(lp.top) > 1:
            shapes[lp.top[1]] = (per_dev,) + tuple(pipe.label_shape)
    return pipes, shapes
