"""Step-pipeline tests: device-side input prefetch, the bounded in-flight
dispatch window, async metric drain/NaN abort, and background snapshots.

The pipeline is numerics-NEUTRAL by construction — it moves host blocking,
never the dispatched step sequence — so the anchor test is bitwise parity
of the final parameters across ``max_in_flight`` in {1, 2, 4}, with device
prefetch + batch-buffer donation on (the default hot path) against the
fully serial loop (prefetch off, window 1).
"""

import glob
import os
import threading
import time

import numpy as np
import pytest

SMALLNET = """
name: "PipeNet"
layers {
  name: "mnist" type: MEMORY_DATA top: "data" top: "label"
  memory_data_param { batch_size: 8 channels: 1 height: 12 width: 12 }
}
layers {
  name: "conv1" type: CONVOLUTION bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 3
    weight_filler { type: "xavier" } bias_filler { type: "constant" } }
}
layers { name: "relu1" type: RELU bottom: "conv1" top: "conv1" }
layers { name: "pool1" type: POOLING bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layers {
  name: "ip1" type: INNER_PRODUCT bottom: "pool1" top: "ip1"
  inner_product_param { num_output: 5
    weight_filler { type: "xavier" } bias_filler { type: "constant" } }
}
layers { name: "loss" type: SOFTMAX_LOSS bottom: "ip1" bottom: "label"
  top: "loss" }
"""


def _solver(max_iter=30, **kw):
    from poseidon_tpu.proto.messages import (SolverParameter,
                                             load_net_from_string)
    return SolverParameter(train_net_param=load_net_from_string(SMALLNET),
                           base_lr=0.05, lr_policy="fixed", momentum=0.9,
                           weight_decay=5e-4, display=10, max_iter=max_iter,
                           random_seed=3, **kw)


def _memory_data(n=256, seed=0, poison=False):
    rs = np.random.RandomState(seed)
    templates = rs.randn(5, 1, 12, 12).astype(np.float32)
    labels = rs.randint(0, 5, size=n)
    data = templates[labels] + \
        0.25 * rs.randn(n, 1, 12, 12).astype(np.float32)
    if poison:
        data[:] = np.nan
    return {"data": data, "label": labels}


def _train_params(tmp_path, sub, **engine_kw):
    import jax
    from poseidon_tpu.runtime.engine import Engine

    out = tmp_path / sub
    out.mkdir()
    eng = Engine(_solver(), memory_data=_memory_data(),
                 output_dir=str(out), **engine_kw)
    try:
        last = eng.train()
        leaves = [np.asarray(v).copy()
                  for v in jax.tree_util.tree_leaves(eng.params)]
        eng._last_feed = eng._device_feed  # survives close() for asserts
        return last, leaves, eng
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# bitwise parity of the pipelined loop
# --------------------------------------------------------------------------- #

def test_max_in_flight_bitwise_parity(tmp_path, monkeypatch):
    """A fixed 30-iteration run produces bitwise-identical final params for
    max_in_flight in {1, 2, 4} with device prefetch on (the default hot
    path), all equal to the fully serial loop — both through the CPU
    passthrough prefetcher AND the real background-thread stage (forced
    on, the accelerator-backend path)."""
    from poseidon_tpu.data.pipeline import DevicePrefetcher

    last_s, serial, _ = _train_params(tmp_path, "serial",
                                      device_prefetch=0, max_in_flight=1)
    assert np.isfinite(last_s["loss"])
    for mif in (1, 2, 4):
        _, leaves, eng = _train_params(tmp_path, f"mif{mif}",
                                       device_prefetch=2, max_in_flight=mif)
        assert eng._use_prefetch  # the prefetch stage actually engaged
        for a, b in zip(serial, leaves):
            np.testing.assert_array_equal(a, b)
    # force the threaded stage (auto resolves to passthrough on CPU)
    monkeypatch.setattr(DevicePrefetcher, "_auto_passthrough",
                        staticmethod(lambda: False))
    _, leaves, eng = _train_params(tmp_path, "threaded",
                                   device_prefetch=2, max_in_flight=2)
    assert eng._last_feed is not None and not eng._last_feed.passthrough
    for a, b in zip(serial, leaves):
        np.testing.assert_array_equal(a, b)


def test_prefetch_disabled_for_stacked_paths(tmp_path):
    """iter_size > 1 and steps_per_dispatch > 1 assemble stacked host
    batches; the prefetcher must stand down (and training still run)."""
    from poseidon_tpu.runtime.engine import Engine

    sp = _solver(max_iter=8)
    sp.iter_size = 2
    eng = Engine(sp, memory_data=_memory_data(), output_dir=str(tmp_path),
                 device_prefetch=2)
    try:
        assert not eng._use_prefetch
        assert np.isfinite(eng.train()["loss"])
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# a display boundary does not drain the device
# --------------------------------------------------------------------------- #

def _display_run(tmp_path, sub, capsys, mif, **solver_kw):
    from poseidon_tpu.runtime.engine import Engine

    out = tmp_path / sub
    out.mkdir()
    eng = Engine(_solver(max_iter=12, **solver_kw), memory_data=_memory_data(),
                 output_dir=str(out), max_in_flight=mif)
    eng.sp.display = 3
    capsys.readouterr()
    try:
        eng.train()
    finally:
        eng.close()
    rows = [(r["iter"], r["loss"]) for r in eng.metrics.rows]
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith(("Iteration", "Snapshot"))]
    return rows, lines


@pytest.mark.parametrize("mif,slow_drain", [(1, False), (2, False),
                                            (4, False), (2, True), (4, True)])
def test_display_rows_are_the_serial_loops(tmp_path, capsys, monkeypatch,
                                           mif, slow_drain):
    """The displayed rows — which iterations, the mean over exactly that
    boundary's steps — are those of the serial loop at every window depth,
    although a boundary no longer waits for the device: it is shown when
    its last step has drained, with later steps already dispatched. With
    the drain slowed (an accelerator's lag, forced here) the rows stay the
    same and each boundary's line still precedes the snapshot taken at
    it."""
    from poseidon_tpu.runtime import metrics

    want, _ = _display_run(tmp_path, "serial", capsys, 1)
    assert [it for it, _ in want] == [3, 6, 9, 12]
    late = []
    if slow_drain:
        real_rows = metrics.scalar_rows

        def slow_rows(m):
            time.sleep(0.05)
            return real_rows(m)

        monkeypatch.setattr(metrics, "scalar_rows", slow_rows)
        monkeypatch.setattr(metrics.AsyncScalarFetcher, "_already_ready",
                            staticmethod(lambda m: False))
        real_put = metrics.AsyncScalarFetcher.put

        def put(self, first_iter, m):
            real_put(self, first_iter, m)
            late.append(self._pending)

        monkeypatch.setattr(metrics.AsyncScalarFetcher, "put", put)
    got, lines = _display_run(tmp_path, "windowed", capsys, mif,
                              snapshot=6, snapshot_prefix="snap/d")
    assert got == want
    shown = [ln.split(",")[0] for ln in lines if ln.startswith("Iteration")]
    assert shown == [f"Iteration {i}" for i in (3, 6, 9, 12)]
    assert lines.index(next(ln for ln in lines if ln.startswith(
        "Iteration 6"))) < lines.index(next(
            ln for ln in lines if ln.startswith("Snapshot")))
    if slow_drain:
        # the loop really ran ahead of the drain at display boundaries too
        assert max(late) == mif - 1


# --------------------------------------------------------------------------- #
# NaN abort rides the async drain
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("mif", [1, 4])
def test_nan_abort_fires_within_window(tmp_path, mif):
    """A non-finite loss aborts the run within max_in_flight dispatches of
    the step that produced it, and the error rewinds to that step."""
    from poseidon_tpu.runtime.engine import Engine, TrainingDivergedError

    eng = Engine(_solver(), memory_data=_memory_data(poison=True),
                 output_dir=str(tmp_path), max_in_flight=mif)
    try:
        with pytest.raises(TrainingDivergedError) as exc:
            eng.train()
        # the poisoned data NaNs the very first step; the report rewinds
        # to it even though the loop may have dispatched further
        assert exc.value.iteration == 0
        assert exc.value.key == "loss"
        dispatched = eng.stats.counters["train_iters"]
        assert dispatched <= exc.value.iteration + 1 + mif
    finally:
        eng.close()


def test_fetcher_window_blocks_and_detects_divergence():
    """AsyncScalarFetcher unit: put() returns only when the window
    INCLUDING its own entry has room for the next dispatch (window 2:
    the first put returns with its entry pending, the second blocks until
    the first drains — so at most 2 dispatches are ever in flight), and
    the drain tags the diverged iteration."""
    from poseidon_tpu.runtime.metrics import AsyncScalarFetcher

    gate = threading.Event()

    class Blocked:
        """Scalar whose materialization (np.asarray) waits on ``gate`` —
        a stand-in for a device value whose step is still running (so
        ``is_ready`` is False until the gate opens and the inline
        fast path must NOT engage)."""

        def __init__(self, v):
            self.v = v

        def is_ready(self):
            return gate.is_set()

        def __array__(self, dtype=None):
            gate.wait(timeout=10.0)
            return np.asarray(self.v, dtype or np.float32)

    f = AsyncScalarFetcher(max_in_flight=2)
    try:
        t0 = time.monotonic()
        f.put(0, {"loss": Blocked(1.0)})  # drainer blocks materializing
        assert time.monotonic() - t0 < 5.0, \
            "window=2 must not block the first put"
        done = threading.Event()

        def second_put():
            f.put(1, {"loss": Blocked(float("nan"))})
            done.set()

        t = threading.Thread(target=second_put, daemon=True)
        t.start()
        time.sleep(0.2)
        assert not done.is_set(), "window=2 must block the second put"
        gate.set()
        t.join(timeout=10.0)
        assert done.is_set()
        rows = f.sync()
        assert [it for it, _ in rows] == [0, 1]
        assert f.divergence is not None and f.divergence[0] == 1
    finally:
        f.close()


def test_fetcher_window_one_is_serial():
    """max_in_flight=1 drains each entry before put() returns — no
    dispatch ever overlaps an unread metric (the serial loop)."""
    from poseidon_tpu.runtime.metrics import AsyncScalarFetcher

    f = AsyncScalarFetcher(max_in_flight=1)
    try:
        for i in range(3):
            f.put(i, {"loss": np.float32(i)})
            # the entry drained before put returned
            drained = f.take_drained()
            assert [it for it, _ in drained] == [i]
    finally:
        f.close()


def test_scalar_rows_expands_scan_chunks():
    from poseidon_tpu.runtime.metrics import scalar_rows

    rows = scalar_rows({"loss": np.asarray([1.0, 2.0, 3.0]),
                        "acc": np.asarray(0.5)})
    assert rows == [{"loss": 1.0, "acc": 0.5}, {"loss": 2.0, "acc": 0.5},
                    {"loss": 3.0, "acc": 0.5}]
    assert scalar_rows({"loss": np.asarray(4.0)}) == [{"loss": 4.0}]


# --------------------------------------------------------------------------- #
# async snapshots
# --------------------------------------------------------------------------- #

def test_async_snapshot_equals_sync_snapshot(tmp_path):
    """The async writer produces the identical artifacts: .caffemodel
    byte-for-byte, .solverstate arrays bitwise (the npz container embeds
    zip timestamps, so bytes are compared per-array)."""
    from poseidon_tpu.runtime.engine import Engine

    sp = _solver(max_iter=6, snapshot_prefix="snap/pipe",
                 snapshot_after_train=True)
    paths = {}
    for mode in ("sync", "async"):
        out = tmp_path / mode
        out.mkdir()
        eng = Engine(sp, memory_data=_memory_data(), output_dir=str(out),
                     async_snapshot=(mode == "async"))
        try:
            eng.train()
        finally:
            eng.close()
        paths[mode] = out / "snap" / "pipe_iter_6"
    with open(f"{paths['sync']}.caffemodel", "rb") as f:
        sync_model = f.read()
    with open(f"{paths['async']}.caffemodel", "rb") as f:
        async_model = f.read()
    assert sync_model == async_model
    a = np.load(f"{paths['sync']}.solverstate.npz")
    b = np.load(f"{paths['async']}.solverstate.npz")
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        assert a[k].tobytes() == b[k].tobytes(), k


def test_async_snapshot_resumes_and_auto_resumes(tmp_path):
    """auto_resume semantics are untouched: a mid-train async snapshot is
    discoverable and restores to the right iteration."""
    from poseidon_tpu.runtime.engine import Engine

    sp = _solver(max_iter=20, snapshot=10, snapshot_prefix="snap/pipe")
    eng = Engine(sp, memory_data=_memory_data(), output_dir=str(tmp_path),
                 async_snapshot=True)
    try:
        eng.train()
    finally:
        eng.close()
    # the mid-train cadence snapshot (iter 10) landed, and auto-resume
    # finds the newest one (the after-train iter-20 write)
    assert (tmp_path / "snap" / "pipe_iter_10.solverstate.npz").exists()
    eng2 = Engine(sp, memory_data=_memory_data(), output_dir=str(tmp_path),
                  async_snapshot=True)
    try:
        restored = eng2.auto_resume()
        assert restored and restored.endswith("pipe_iter_20.solverstate.npz")
        assert int(eng2.state.solver.it) == 20
    finally:
        eng2.close()


def test_torn_async_writer_shutdown_leaves_no_partial_files(tmp_path,
                                                            monkeypatch):
    """A writer that dies mid-write must leave at worst *.tmp.<pid> litter
    (collected by sweep_stale_tmp) — never a truncated real-suffix file —
    and the failure surfaces loudly on the next wait()."""
    import jax
    from poseidon_tpu.runtime import checkpoint as ckpt
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.parallel import init_train_state
    from poseidon_tpu.proto.messages import load_net_from_string

    shapes = {"data": (8, 1, 12, 12), "label": (8,)}
    net = Net(load_net_from_string(SMALLNET), "TRAIN", source_shapes=shapes)
    params = net.init(jax.random.PRNGKey(0))
    state = init_train_state(params)
    prefix = str(tmp_path / "snap" / "torn")

    real_savez = np.savez

    def dying_savez(f, **arrays):
        f.write(b"partial bytes that must never land at the real name")
        raise IOError("disk vanished mid-write")

    monkeypatch.setattr(ckpt.np, "savez", dying_savez)
    w = ckpt.AsyncSnapshotWriter()
    w.submit(prefix, net, params, state)
    with pytest.raises(IOError):
        w.wait()
    # the torn write left only tmp litter; no real-suffix solverstate
    assert glob.glob(f"{prefix}*.solverstate.npz") == []
    litter = glob.glob(f"{prefix}*.tmp.*")
    assert litter, "the torn write should have left its tmp behind"
    removed = ckpt.sweep_stale_tmp(prefix, min_age_s=0.0)
    assert sorted(removed) == sorted(litter), "litter must be swept"
    # and the writer recovers: a healthy write lands both artifacts
    monkeypatch.setattr(ckpt.np, "savez", real_savez)
    w.submit(prefix, net, params, state)
    model, statef = w.wait()
    assert os.path.exists(model) and os.path.exists(statef)
    w.close()


def test_async_snapshot_failure_aborts_at_next_sync_boundary(tmp_path,
                                                             monkeypatch):
    """A failed BACKGROUND snapshot write must abort the run at the next
    sync boundary (the following snapshot cadence point, or end-of-train)
    with the writer's original error — never train to completion as if
    the snapshot existed, which would leave auto-resume pointing at
    nothing. Pinned for the elasticity story: preemptible fleets lean on
    snapshots + rejoin, so a silently-lost snapshot is a silently-lost
    worker contribution on the next restart."""
    from poseidon_tpu.runtime import checkpoint as ckpt
    from poseidon_tpu.runtime.engine import Engine

    def dying_savez(f, **arrays):
        raise IOError("disk vanished mid-write")

    monkeypatch.setattr(ckpt.np, "savez", dying_savez)
    sp = _solver(max_iter=30, snapshot=5, snapshot_prefix="snap/die")
    eng = Engine(sp, memory_data=_memory_data(),
                 output_dir=str(tmp_path), async_snapshot=True)
    try:
        with pytest.raises(IOError, match="disk vanished"):
            eng.train()
        # the abort landed at the NEXT snapshot boundary after the failed
        # iter-5 write (iter 10's submit joins the dead iter-5 thread) —
        # not at end-of-train 20 iterations later
        assert eng.iteration() <= 10, (
            f"failure surfaced only at iteration {eng.iteration()}; the "
            f"iter-10 sync boundary should have re-raised it")
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# device prefetcher: failure propagation + fault-injection interop
# --------------------------------------------------------------------------- #

def test_device_prefetcher_propagates_source_failure():
    """A dying pipeline worker surfaces on __next__ instead of wedging."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from poseidon_tpu.data.pipeline import DevicePrefetcher
    from poseidon_tpu.parallel import make_mesh

    class DyingPipe:
        def __init__(self):
            self.n = 0

        def __next__(self):
            self.n += 1
            if self.n > 2:
                raise IOError("record store vanished")
            return {"data": np.zeros((8, 4), np.float32)}

    sharding = NamedSharding(make_mesh(), P("data"))
    # passthrough=False forces the background thread (the accelerator
    # path; auto resolves to passthrough on the CPU suite backend)
    for passthrough in (False, True):
        feed = DevicePrefetcher([DyingPipe()], sharding, depth=2,
                                passthrough=passthrough)
        try:
            seen = 0
            with pytest.raises(IOError, match="vanished"):
                for _ in range(4):
                    np.asarray(next(feed)["data"])
                    seen += 1
            assert seen == 2
            # the death is sticky: a retried dequeue re-raises immediately
            # instead of blocking forever on a dead worker's empty queue
            with pytest.raises(IOError, match="vanished"):
                next(feed)
        finally:
            feed.close()


def test_nan_is_never_snapshotted(tmp_path):
    """A snapshot boundary is a hard sync point: params poisoned by a NaN
    the drainer has not yet surfaced must never be persisted (and then
    silently auto-resumed) — the divergence aborts BEFORE the write."""
    from poseidon_tpu.runtime.engine import Engine, TrainingDivergedError

    sp = _solver(max_iter=30, snapshot=2, snapshot_prefix="snap/poison")
    eng = Engine(sp, memory_data=_memory_data(poison=True),
                 output_dir=str(tmp_path), max_in_flight=4)
    try:
        with pytest.raises(TrainingDivergedError):
            eng.train()
    finally:
        eng.close()
    assert glob.glob(str(tmp_path / "snap" / "*.solverstate.npz")) == []
    assert glob.glob(str(tmp_path / "snap" / "*.caffemodel")) == []


def test_device_prefetch_faultproxy_async_tier_interop(tmp_path,
                                                       monkeypatch):
    """Device prefetch composes with the fault-injection harness: an
    async-SSP worker whose ONLY cross-process channel rides a FaultProxy
    delay rule (slow != dead) trains to completion with the prefetcher
    feeding device-resident batches, and its clocks land on the service."""
    import jax
    from poseidon_tpu.parallel.async_ssp import ParamService
    from poseidon_tpu.runtime.engine import Engine
    from poseidon_tpu.runtime.faults import FaultProxy, FaultRule

    # seed the service with the engine's exact param tree structure
    probe = Engine(_solver(max_iter=1), memory_data=_memory_data(),
                   output_dir=str(tmp_path))
    host = {l: {p: np.asarray(v, np.float32) for p, v in ps.items()}
            for l, ps in probe.params.items()}
    probe.close()

    svc = ParamService(host, n_workers=2, liveness_timeout_s=0.0)
    proxy = FaultProxy(("127.0.0.1", svc.port))
    proxy.add_rule(FaultRule(action="delay", delay_s=0.005))
    monkeypatch.setenv("POSEIDON_PROC_ID", "1")
    monkeypatch.setenv("POSEIDON_NUM_PROCS", "2")
    monkeypatch.delenv("POSEIDON_COORDINATOR", raising=False)
    try:
        eng = Engine(_solver(max_iter=6), memory_data=_memory_data(),
                     output_dir=str(tmp_path), device_prefetch=2,
                     max_in_flight=2,
                     async_ssp={"staleness": 8, "sync_every": 1,
                                "service_port": proxy.port})
        try:
            last = eng.train()
            assert np.isfinite(last["loss"])
            assert eng._use_prefetch
        finally:
            eng.close()
        assert svc.clocks[1] >= 5, svc.clocks
    finally:
        proxy.close()
        svc.close()
