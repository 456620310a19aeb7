"""Serving-tier suite (ISSUE 2): bucketed AOT executor parity, dynamic
micro-batcher triggers, backpressure, checkpoint hot-reload, fault-proxy
chaos, and graceful shutdown.

Everything here is CPU-safe and binds port 0 on loopback only — no fixed
ports, no flakes — so the whole file runs under the tier-1 command. The
chaos tests reuse runtime/faults.py's deterministic proxy (exact byte
counts and connection indices, nothing random).
"""

import os
import threading
import time

import numpy as np
import pytest

pytestmark = pytest.mark.serving

DEPLOY_NET = """
name: "servnet"
input: "data"
input_dim: 1 input_dim: 3 input_dim: 8 input_dim: 8
layers { name: "conv" type: CONVOLUTION bottom: "data" top: "conv"
  convolution_param { num_output: 4 kernel_size: 3
    weight_filler { type: "xavier" } } }
layers { name: "fc" type: INNER_PRODUCT bottom: "conv" top: "fc"
  inner_product_param { num_output: 3 weight_filler { type: "xavier" } } }
layers { name: "prob" type: SOFTMAX bottom: "fc" top: "prob" }
"""


def _build_executor(buckets=(1, 2, 4)):
    import jax
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.proto.messages import load_net_from_string
    from poseidon_tpu.serving.executor import BucketedExecutor

    net = Net(load_net_from_string(DEPLOY_NET), "TEST")
    params = net.init(jax.random.PRNGKey(7))
    return BucketedExecutor(net, params, buckets=buckets)


def _rows(n, seed=0):
    return np.random.RandomState(seed).randn(n, 3, 8, 8).astype(np.float32)


# --------------------------------------------------------------------------- #
# executor: bucketed AOT cache
# --------------------------------------------------------------------------- #

def test_bucketed_executor_matches_direct_jit():
    """Padding to a bucket and slicing back is BIT-IDENTICAL to a direct
    jit forward at the BUCKET's shape whatever the padding rows hold (row
    independence: that is what the executor adds), and within a few f32
    ulps of a direct forward at the request's own shape. The second is
    not bitwise under the installed XLA:CPU: a batch-3 and a batch-4
    program block the FC's contraction differently, so 3 rows served from
    the 4-bucket differ from the batch-3 program by up to 3 ulps (8.9e-8
    at p ~ 0.3); a request that fills its bucket is bitwise equal."""
    import jax

    ex = _build_executor()
    direct = jax.jit(lambda p, i: ex.net.apply(p, i, train=False).outputs)
    for n in (1, 2, 3, 4):
        x = _rows(n, seed=n)
        got = ex.infer({"data": x})["prob"]
        assert got.shape == (n, 3)
        pad = _rows(ex.bucket_for(n) - n, seed=99) * 1e3
        at_bucket = direct(ex._params, {"data": np.concatenate([x, pad])})
        np.testing.assert_array_equal(
            got, np.asarray(at_bucket["prob"])[:n])
        want = np.asarray(direct(ex._params, {"data": x})["prob"])
        if ex.bucket_for(n) == n:
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_array_max_ulp(got, want, maxulp=8)


def test_bucket_selection_padding_and_limits():
    ex = _build_executor()
    assert [ex.bucket_for(n) for n in (1, 2, 3, 4)] == [1, 2, 4, 4]
    ex.infer({"data": _rows(3)})
    assert ex.calls[4] == 1 and ex.rows_padded == 1 and ex.rows_served == 3
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        ex.infer({"data": _rows(5)})
    with pytest.raises(ValueError, match="row shape"):
        ex.infer({"data": np.zeros((1, 3, 4, 4), np.float32)})


def test_executor_warm_precompiles_no_request_trace():
    """Every bucket's executable exists before the first request."""
    ex = _build_executor(buckets=(1, 2))
    assert sorted(ex._compiled) == [1, 2]


def test_swap_params_validates_and_applies():
    import jax

    ex = _build_executor(buckets=(2,))
    x = _rows(2)
    before = ex.infer({"data": x})["prob"]
    doubled = jax.tree_util.tree_map(lambda v: v * 2.0, ex._params)
    assert ex.swap_params(doubled) == 1
    after = ex.infer({"data": x})["prob"]
    assert not np.allclose(before, after)
    # wrong tree shape is refused (the executables are shape-keyed)
    bad = {"conv": {"w": np.zeros((1, 1), np.float32)}}
    with pytest.raises(ValueError):
        ex.swap_params(bad)
    assert ex.params_version == 1


# --------------------------------------------------------------------------- #
# batcher: flush triggers, backpressure, deadlines
# --------------------------------------------------------------------------- #

class FakeExecutor:
    """Duck-typed executor: records flushed batch sizes, optional per-call
    stall (to hold the flush thread busy deterministically)."""

    def __init__(self, max_batch=4, delay_s=0.0):
        self.input_names = ["x"]
        self.max_batch = max_batch
        self.delay_s = delay_s
        self.batch_rows = []
        self.calls = {max_batch: 0}
        self.rows_served = 0
        self.rows_padded = 0
        self.params_version = 0

    def infer(self, inputs):
        if self.delay_s:
            time.sleep(self.delay_s)
        rows = int(np.shape(inputs["x"])[0])
        self.batch_rows.append(rows)
        self.rows_served += rows
        self.calls[self.max_batch] += 1
        return {"y": np.asarray(inputs["x"], np.float32) * 2.0}


def test_batcher_flushes_on_size_trigger():
    """A full batch dispatches immediately — max_delay_s is huge, so only
    the SIZE trigger can explain a fast flush."""
    from poseidon_tpu.serving.batcher import DynamicBatcher

    ex = FakeExecutor(max_batch=4)
    b = DynamicBatcher(ex, max_delay_s=30.0, max_queue=16)
    try:
        results = [None] * 4
        ts = []
        for i in range(4):
            t = threading.Thread(
                target=lambda i=i: results.__setitem__(
                    i, b.submit({"x": np.full((1, 2), i, np.float32)})),
                daemon=True)
            ts.append(t)
        t0 = time.monotonic()
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=10.0)
        assert time.monotonic() - t0 < 5.0, "size trigger did not fire"
        assert all(r is not None for r in results)
        # each caller got ITS rows back (fan-out slicing)
        for i, r in enumerate(results):
            np.testing.assert_array_equal(r["y"],
                                          np.full((1, 2), 2.0 * i))
        assert ex.batch_rows and max(ex.batch_rows) == 4
    finally:
        b.close()


def test_batcher_flushes_on_deadline_trigger():
    """A lone request never waits past max_delay_s for company."""
    from poseidon_tpu.serving.batcher import DynamicBatcher

    ex = FakeExecutor(max_batch=64)
    b = DynamicBatcher(ex, max_delay_s=0.05, max_queue=16)
    try:
        t0 = time.monotonic()
        out = b.submit({"x": np.ones((1, 2), np.float32)})
        waited = time.monotonic() - t0
        assert out["y"].shape == (1, 2)
        assert waited < 2.0
        assert ex.batch_rows == [1]          # partial batch: deadline fired
        assert b.fill_ratio() < 1.0
    finally:
        b.close()


def test_full_queue_sheds_explicitly_not_a_hang():
    """Admission control: with the flush thread held busy and the queue at
    max_queue, the next submit raises ShedError IMMEDIATELY."""
    from poseidon_tpu.serving.batcher import DynamicBatcher, ShedError

    ex = FakeExecutor(max_batch=1, delay_s=0.6)
    b = DynamicBatcher(ex, max_delay_s=0.0, max_queue=2)
    try:
        threads = []
        for i in range(3):  # 1 in-flight (popped) + 2 queued
            t = threading.Thread(
                target=lambda: b.submit({"x": np.ones((1, 1), np.float32)},
                                        timeout_s=30.0),
                daemon=True)
            t.start()
            threads.append(t)
            time.sleep(0.08)   # let the flush thread pop the first one
        t0 = time.monotonic()
        with pytest.raises(ShedError, match="queue full"):
            b.submit({"x": np.ones((1, 1), np.float32)})
        assert time.monotonic() - t0 < 0.5, "shed must be immediate"
        assert b.shed_count == 1
        for t in threads:
            t.join(timeout=10.0)
    finally:
        b.close()


def test_request_deadline_expires_in_queue():
    from poseidon_tpu.serving.batcher import DeadlineError, DynamicBatcher

    ex = FakeExecutor(max_batch=1, delay_s=0.3)
    b = DynamicBatcher(ex, max_delay_s=0.0, max_queue=8)
    try:
        # occupy the flush thread, then submit with a deadline shorter than
        # the stall: by dispatch time it has expired
        blocker = threading.Thread(
            target=lambda: b.submit({"x": np.ones((1, 1), np.float32)}),
            daemon=True)
        blocker.start()
        time.sleep(0.05)
        with pytest.raises(DeadlineError):
            b.submit({"x": np.ones((1, 1), np.float32)}, deadline_s=0.01)
        assert b.deadline_expired == 1
        blocker.join(timeout=10.0)
    finally:
        b.close()


def test_oversized_request_rejected():
    from poseidon_tpu.serving.batcher import DynamicBatcher

    ex = FakeExecutor(max_batch=2)
    b = DynamicBatcher(ex, max_delay_s=0.0)
    try:
        with pytest.raises(ValueError, match="split it client-side"):
            b.submit({"x": np.ones((3, 1), np.float32)})
    finally:
        b.close()


# --------------------------------------------------------------------------- #
# server + client: roundtrip, stats op, containment
# --------------------------------------------------------------------------- #

def _serve(executor=None, **kw):
    from poseidon_tpu.serving.server import InferenceServer

    return InferenceServer(executor or _build_executor(),
                           max_delay_s=kw.pop("max_delay_s", 0.002), **kw)


def test_server_roundtrip_and_stats_op():
    from poseidon_tpu.serving.client import ServingClient

    srv = _serve()
    cli = ServingClient(srv.addr)
    try:
        x = _rows(2)
        out = cli.infer({"data": x})
        np.testing.assert_array_equal(
            out["prob"], srv.executor.infer({"data": x})["prob"])
        st = cli.stats()
        for key in ("latency", "queue_depth", "batch_fill", "shed",
                    "bucket_calls", "reloads", "params_version"):
            assert key in st, f"stats op missing {key}"
        assert st["latency"]["count"] >= 1
        assert st["shed"] == 0
    finally:
        cli.close()
        srv.shutdown()


def test_malformed_frame_drops_one_connection_not_the_server():
    """ParamService containment pattern: garbage from one peer kills ITS
    connection; the next client is served normally."""
    import socket as _socket

    from poseidon_tpu.serving.client import ServingClient

    srv = _serve()
    try:
        sk = _socket.create_connection(srv.addr)
        sk.sendall(b"GET / HTTP/1.1\r\nHost: nope\r\n\r\n")  # not a frame
        # server must close THIS connection (bad header -> oversized length
        # -> FrameError); clean FIN or RST both count as dropped
        sk.settimeout(5.0)
        try:
            assert sk.recv(1) == b""
        except ConnectionError:
            pass
        sk.close()
        cli = ServingClient(srv.addr)
        try:
            out = cli.infer({"data": _rows(1)})
            assert out["prob"].shape == (1, 3)
        finally:
            cli.close()
        assert srv.bad_frames >= 1
    finally:
        srv.shutdown()


def test_absurd_length_prefix_rejected_before_allocation(monkeypatch):
    """PROTO207's fix (proto/wire.py): a frame header claiming an absurd
    length is refused as a FrameError BEFORE any payload allocation —
    the configurable cap (POSEIDON_MAX_FRAME_BYTES /
    set_max_frame_bytes), not a multi-gigabyte recv buffer, decides.
    The offending connection dies; the server keeps serving."""
    import socket as _socket
    import struct as _struct

    from poseidon_tpu.proto import wire
    from poseidon_tpu.serving.client import ServingClient

    # pin the ambient environment: an operator legitimately exporting
    # the knob must not change what this test asserts about defaults
    monkeypatch.delenv(wire.MAX_FRAME_ENV, raising=False)

    # unit level: the cap knob resolves override > env > default and the
    # recv path refuses an over-cap header without reading the payload
    assert wire.max_frame_bytes() == wire.DEFAULT_MAX_FRAME
    wire.set_max_frame_bytes(1024)
    try:
        assert wire.max_frame_bytes() == 1024
        with pytest.raises(ValueError):
            wire.set_max_frame_bytes(0)
    finally:
        wire.set_max_frame_bytes(None)
    monkeypatch.setenv(wire.MAX_FRAME_ENV, "4096")
    assert wire.max_frame_bytes() == 4096
    monkeypatch.delenv(wire.MAX_FRAME_ENV)

    srv = _serve()
    try:
        sk = _socket.create_connection(srv.addr)
        # a "legitimate"-looking header claiming a 2**62-byte frame: the
        # server must drop the connection at the cap check (loudly, as a
        # bad frame), never attempt the recv
        sk.sendall(_struct.pack("!Q", 1 << 62))
        sk.settimeout(5.0)
        try:
            assert sk.recv(1) == b""
        except ConnectionError:
            pass
        sk.close()
        # send-side refusal names the knob instead of wedging the peer —
        # and is deliberately NOT a ConnectionError/FrameError, so the
        # reconnect-and-replay machinery can never retry a deterministic
        # over-cap frame for the whole backoff deadline
        class _FakeSock:
            def sendall(self, data):
                raise AssertionError("oversized frame reached the socket")
        wire.set_max_frame_bytes(64)
        try:
            with pytest.raises(wire.FrameTooLargeError,
                               match="POSEIDON_MAX_FRAME"):
                wire.send_frame(_FakeSock(), b"x" * 1024)
            assert not issubclass(wire.FrameTooLargeError, ConnectionError)
        finally:
            wire.set_max_frame_bytes(None)
        # an unusable env value warns instead of silently reverting
        monkeypatch.setenv(wire.MAX_FRAME_ENV, "2GB")
        with pytest.warns(RuntimeWarning, match="not a positive integer"):
            assert wire.max_frame_bytes() == wire.DEFAULT_MAX_FRAME
        monkeypatch.delenv(wire.MAX_FRAME_ENV)
        # the server survived and still serves
        cli = ServingClient(srv.addr)
        try:
            out = cli.infer({"data": _rows(1)})
            assert out["prob"].shape == (1, 3)
        finally:
            cli.close()
        assert srv.bad_frames >= 1
    finally:
        srv.shutdown()


def test_unknown_kind_gets_error_reply():
    from poseidon_tpu.proto.wire import recv_frame, send_frame
    import socket as _socket

    srv = _serve()
    try:
        sk = _socket.create_connection(srv.addr)
        send_frame(sk, {"kind": "no-such-op"})
        reply = recv_frame(sk)
        assert reply["ok"] is False and "no-such-op" in reply["error"]
        # connection survives a bad REQUEST (only torn frames drop it)
        send_frame(sk, {"kind": "health"})
        assert recv_frame(sk)["ok"] is True
        sk.close()
    finally:
        srv.shutdown()


# --------------------------------------------------------------------------- #
# checkpoint hot-reload
# --------------------------------------------------------------------------- #

def _snapshot_params(prefix, net, params, it):
    """Write a real <prefix>_iter_<it>.solverstate.npz + .caffemodel pair
    through the training tier's own snapshot writer."""
    import jax.numpy as jnp
    from poseidon_tpu.parallel.trainer import init_train_state
    from poseidon_tpu.runtime.checkpoint import snapshot

    state = init_train_state(params)
    state = state._replace(solver=state.solver._replace(
        it=jnp.asarray(it, jnp.int32)))
    return snapshot(prefix, net, params, state)


def test_hot_reload_swaps_params_mid_stream(tmp_path):
    """A newer snapshot swaps in atomically: concurrent in-flight requests
    NEVER error, and results flip from old-params to new-params output."""
    import jax

    from poseidon_tpu.serving.client import ServingClient
    from poseidon_tpu.serving.reloader import CheckpointReloader

    ex = _build_executor(buckets=(1, 2, 4))
    prefix = str(tmp_path / "snap" / "servnet")
    _snapshot_params(prefix, ex.net, ex._params, it=1)
    reloader = CheckpointReloader(ex, prefix, start=False)
    assert reloader.check_now() is True      # picks up iter 1 immediately
    assert reloader.reloads == 1

    srv = _serve(ex, reloader=reloader)
    x = _rows(2)
    before = None
    errors = []
    stop = threading.Event()

    def hammer():
        from poseidon_tpu.serving.client import ServingClient as C
        c = C(srv.addr)
        try:
            while not stop.is_set():
                try:
                    c.infer({"data": x})
                except Exception as e:  # noqa: BLE001 — the assertion
                    errors.append(e)
                    return
        finally:
            c.close()

    cli = ServingClient(srv.addr)
    try:
        before = cli.infer({"data": x})["prob"]
        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        doubled = jax.tree_util.tree_map(lambda v: v * 2.0, ex._params)
        _snapshot_params(prefix, ex.net, doubled, it=2)
        reply = cli.reload()
        assert reply["ok"] and reply["reloaded"] is True
        after = cli.infer({"data": x})["prob"]
        time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        assert not errors, f"in-flight request errored during reload: " \
                           f"{errors[0]}"
        assert not np.allclose(before, after)
        assert ex.params_version == 2        # initial pickup + hot reload
    finally:
        stop.set()
        cli.close()
        srv.shutdown()


def test_reloader_ignores_tmp_litter_and_survives_torn_snapshot(tmp_path):
    """tmp litter is invisible to discovery; a corrupt newest snapshot is
    counted and skipped — the server keeps serving the previous params."""
    from poseidon_tpu.serving.reloader import CheckpointReloader

    ex = _build_executor(buckets=(1,))
    prefix = str(tmp_path / "snap" / "servnet")
    _snapshot_params(prefix, ex.net, ex._params, it=1)
    rel = CheckpointReloader(ex, prefix, start=False)
    assert rel.check_now() is True
    # a writer killed mid-snapshot leaves tmp litter: never a reload
    litter = tmp_path / "snap" / "servnet_iter_9.solverstate.npz.tmp.12345"
    litter.write_bytes(b"\x00" * 64)
    assert rel.check_now() is False
    # a torn "complete-looking" file: load fails, old params keep serving
    torn = tmp_path / "snap" / "servnet_iter_10.solverstate.npz"
    torn.write_bytes(b"not-an-npz")
    ver_before = ex.params_version
    assert rel.check_now() is False
    assert rel.failed_reloads == 1 and rel.last_error
    assert ex.params_version == ver_before
    out = ex.infer({"data": _rows(1)})
    assert out["prob"].shape == (1, 3)


def test_reloader_seeded_with_serving_snapshot_never_reswaps(tmp_path):
    """Seeding current_path with the snapshot --weights already loaded
    means the first poll is a no-op (no redundant re-load, no backwards
    swap); only a strictly newer snapshot triggers a reload."""
    import jax

    from poseidon_tpu.serving.reloader import CheckpointReloader

    ex = _build_executor(buckets=(1,))
    prefix = str(tmp_path / "snap" / "servnet")
    _, state_path = _snapshot_params(prefix, ex.net, ex._params, it=5)
    rel = CheckpointReloader(ex, prefix, start=False,
                             current_path=state_path)
    assert rel.check_now() is False and rel.reloads == 0
    # an OLDER snapshot appearing later must not regress the serving params
    _snapshot_params(prefix, ex.net, ex._params, it=3)
    assert rel.check_now() is False
    # a strictly newer one swaps
    _snapshot_params(prefix, ex.net,
                     jax.tree_util.tree_map(lambda v: v * 2.0, ex._params),
                     it=9)
    assert rel.check_now() is True and rel.reloads == 1


def test_reloader_background_thread_polls(tmp_path):
    import jax

    from poseidon_tpu.serving.reloader import CheckpointReloader

    ex = _build_executor(buckets=(1,))
    prefix = str(tmp_path / "snap" / "servnet")
    rel = CheckpointReloader(ex, prefix, poll_s=0.05)
    try:
        _snapshot_params(prefix, ex.net,
                         jax.tree_util.tree_map(lambda v: v * 3.0,
                                                ex._params), it=1)
        deadline = time.time() + 10.0
        while rel.reloads < 1:
            assert time.time() < deadline, "watcher never picked up snapshot"
            time.sleep(0.02)
    finally:
        rel.close()


# --------------------------------------------------------------------------- #
# chaos: runtime/faults.py proxy between client and server
# --------------------------------------------------------------------------- #

def test_server_survives_fault_proxy_chaos():
    """drop + truncate + sever rules between client and server: the client
    retries through every cut via retry_with_backoff; the server contains
    the torn frames and keeps serving."""
    from poseidon_tpu.runtime.faults import FaultProxy, FaultRule
    from poseidon_tpu.serving.client import ServingClient

    srv = _serve()
    proxy = FaultProxy(srv.addr)
    # conn 0: accepted then closed (dead LB slot) — exercises redial
    proxy.add_rule(FaultRule(action="drop", conn=0))
    # conn 1: congested hop — slow must NOT read as dead (no reconnect)
    proxy.add_rule(FaultRule(action="delay", conn=1, delay_s=0.05))
    # conn 2: cut after 40 bytes of request — a torn frame mid-request
    proxy.add_rule(FaultRule(action="truncate", conn=2, after_bytes=40))
    try:
        cli = ServingClient(proxy.addr, retry_deadline_s=10.0,
                            backoff_base_s=0.01, backoff_cap_s=0.05)
        x = _rows(1)
        want = srv.executor.infer({"data": x})["prob"]
        out1 = cli.infer({"data": x})          # conn0 dropped -> conn1 works
        np.testing.assert_array_equal(out1["prob"], want)
        reconnects_after_delay = cli.reconnects
        out_slow = cli.infer({"data": x})      # still conn1, delayed chunks
        np.testing.assert_array_equal(out_slow["prob"], want)
        assert cli.reconnects == reconnects_after_delay, \
            "a delayed (slow-but-alive) channel must not trigger reconnect"
        proxy.sever_all()                      # hard partition mid-run
        out2 = cli.infer({"data": x})          # conn2 truncated -> conn3
        np.testing.assert_array_equal(out2["prob"], want)
        assert proxy.accepted >= 4
        assert srv.bad_frames >= 1             # the torn frame was contained
        cli.close()
        # the server itself never wedged: a direct client still works
        direct = ServingClient(srv.addr)
        assert direct.infer({"data": x})["prob"].shape == (1, 3)
        direct.close()
    finally:
        proxy.close()
        srv.shutdown()


def test_kill_mid_request_client_reconnects_and_completes():
    """The acceptance scenario: the connection dies at an exact byte count
    MID-REQUEST; the client redials via retry_with_backoff, resends, and
    completes — the caller never sees the cut."""
    from poseidon_tpu.runtime.faults import FaultProxy, FaultRule
    from poseidon_tpu.serving.client import ServingClient

    srv = _serve()
    proxy = FaultProxy(srv.addr)
    # the FIRST connection is cut 40 bytes into the request frame — past the
    # wire-codec negotiation frame the client sends during _dial (header +
    # pickled offer), so the cut tears the request itself, not the dial
    import pickle
    from poseidon_tpu.proto.wire import WIRE_CODEC_VERSION
    neg = pickle.dumps({"kind": "wire", "codec": WIRE_CODEC_VERSION},
                       protocol=pickle.HIGHEST_PROTOCOL)
    proxy.add_rule(FaultRule(action="sever", conn=0,
                             after_bytes=len(neg) + 8 + 40))
    try:
        cli = ServingClient(proxy.addr, retry_deadline_s=10.0,
                            backoff_base_s=0.01, backoff_cap_s=0.05)
        x = _rows(2, seed=3)
        out = cli.infer({"data": x})
        np.testing.assert_array_equal(
            out["prob"], srv.executor.infer({"data": x})["prob"])
        assert cli.reconnects >= 1
        cli.close()
    finally:
        proxy.close()
        srv.shutdown()


def test_shed_response_is_explicit_over_the_wire():
    """Backpressure reaches the CLIENT as a structured shed reply, not a
    stall: hold the flush thread busy, fill the queue, assert the next
    request's refusal arrives fast and flagged."""
    from poseidon_tpu.serving.client import ServingClient, ServingError
    from poseidon_tpu.serving.server import InferenceServer

    ex = FakeExecutor(max_batch=1, delay_s=0.6)
    srv = InferenceServer(ex, max_delay_s=0.0, max_queue=2)
    try:
        hammers = []
        for _ in range(3):
            c = ServingClient(srv.addr)
            t = threading.Thread(
                target=lambda c=c: c.infer({"x": np.ones((1, 1),
                                                         np.float32)}),
                daemon=True)
            t.start()
            hammers.append((c, t))
            time.sleep(0.08)
        cli = ServingClient(srv.addr)
        t0 = time.monotonic()
        with pytest.raises(ServingError) as ei:
            cli.infer({"x": np.ones((1, 1), np.float32)})
        assert ei.value.shed is True
        assert time.monotonic() - t0 < 0.5
        st = cli.stats()
        assert st["shed"] >= 1
        cli.close()
        for c, t in hammers:
            t.join(timeout=10.0)
            c.close()
    finally:
        srv.shutdown()


def test_malformed_request_rejected_at_admission_not_cobatched():
    """A wrong-shaped request is refused with ITS error at submit time;
    a concurrent valid request in the same flush window is unaffected."""
    from poseidon_tpu.serving.client import ServingClient, ServingError

    srv = _serve(max_delay_s=0.05)        # window wide enough to co-batch
    good_cli = ServingClient(srv.addr)
    bad_cli = ServingClient(srv.addr)
    try:
        results = {}

        def good():
            results["good"] = good_cli.infer({"data": _rows(2)})

        t = threading.Thread(target=good, daemon=True)
        t.start()
        with pytest.raises(ServingError, match="row shape"):
            bad_cli.infer({"data": np.zeros((1, 3, 4, 4), np.float32)})
        t.join(timeout=10.0)
        assert results["good"]["prob"].shape == (2, 3)
    finally:
        good_cli.close()
        bad_cli.close()
        srv.shutdown()


def test_executor_failure_is_server_error_not_bad_frame():
    """A server-side executor crash reaches the client as server_error —
    never billed to the client's frame hygiene."""
    from poseidon_tpu.serving.client import ServingClient, ServingError
    from poseidon_tpu.serving.server import InferenceServer

    class ExplodingExecutor(FakeExecutor):
        def infer(self, inputs):
            raise RuntimeError("XLA device exploded")

    srv = InferenceServer(ExplodingExecutor(max_batch=2), max_delay_s=0.0)
    cli = ServingClient(srv.addr)
    try:
        with pytest.raises(ServingError, match="exploded") as ei:
            cli.infer({"x": np.ones((1, 1), np.float32)})
        assert not ei.value.shed and not ei.value.deadline_exceeded
        assert srv.server_errors == 1
        assert srv.bad_frames == 0
    finally:
        cli.close()
        srv.shutdown()


# --------------------------------------------------------------------------- #
# graceful shutdown
# --------------------------------------------------------------------------- #

def test_graceful_shutdown_drains_no_request_silently_dropped():
    """Every request admitted before the stop gets a REPLY (result, not a
    dropped socket); new connections are refused afterwards."""
    import socket as _socket

    from poseidon_tpu.serving.client import ServingClient
    from poseidon_tpu.serving.server import InferenceServer

    ex = FakeExecutor(max_batch=1, delay_s=0.15)
    srv = InferenceServer(ex, max_delay_s=0.0, max_queue=32)
    results, errors = [], []

    def one_request():
        c = ServingClient(srv.addr, retry_deadline_s=1.0)
        try:
            results.append(c.infer({"x": np.ones((1, 1), np.float32)}))
        except Exception as e:  # noqa: BLE001 — the assertion
            errors.append(e)
        finally:
            c.close()

    threads = [threading.Thread(target=one_request, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.1)            # requests admitted / in flight
    srv.request_stop()
    srv.shutdown(drain=True)
    for t in threads:
        t.join(timeout=15.0)
    assert not errors, f"request dropped during drain: {errors[0]}"
    assert len(results) == 4
    assert all(r["y"].shape == (1, 1) for r in results)
    # listener is closed: a fresh connection is refused
    with pytest.raises(OSError):
        _socket.create_connection(srv.addr, timeout=0.5)


def test_submissions_after_stop_get_shed_reply():
    from poseidon_tpu.serving.batcher import DynamicBatcher, ShedError

    ex = FakeExecutor(max_batch=2)
    b = DynamicBatcher(ex, max_delay_s=0.0)
    b.close(drain=True)
    with pytest.raises(ShedError, match="shutting down"):
        b.submit({"x": np.ones((1, 1), np.float32)})


def test_serve_cli_sigterm_exits_zero(tmp_path):
    """`python -m poseidon_tpu serve` handles SIGTERM by draining and
    exiting 0 with a final stats line (the ops contract)."""
    import signal
    import subprocess
    import sys

    model = tmp_path / "deploy.prototxt"
    model.write_text(DEPLOY_NET)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "poseidon_tpu", "serve",
         "--model", str(model), "--buckets", "1,2", "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
    addr = None
    try:
        deadline = time.time() + 120.0
        for line in proc.stdout:
            if "listening on" in line:
                host, port = line.rsplit(" ", 1)[-1].strip().split(":")
                addr = (host, int(port))
                break
            assert time.time() < deadline, "server never came up"
        assert addr is not None
        from poseidon_tpu.serving.client import ServingClient
        cli = ServingClient(addr, connect_deadline_s=10.0)
        out = cli.infer({"data": _rows(1)})
        assert out["prob"].shape == (1, 3)
        cli.close()
        proc.send_signal(signal.SIGTERM)
        rest = proc.stdout.read()
        rc = proc.wait(timeout=30.0)
        assert rc == 0, f"serve exited {rc}: {rest[-2000:]}"
        assert "serving_final_stats" in rest
    finally:
        if proc.poll() is None:
            proc.kill()


# --------------------------------------------------------------------------- #
# CLI + bench plumbing
# --------------------------------------------------------------------------- #

def test_cli_serve_parser_defaults():
    from poseidon_tpu.runtime.cli import build_parser

    args = build_parser().parse_args(["serve", "--model", "m.prototxt"])
    # unset --buckets falls back to the executor's own ladder
    assert args.buckets == "" and args.port == 0
    from poseidon_tpu.runtime.cli import _build_serving_executor
    from poseidon_tpu.serving.executor import DEFAULT_BUCKETS
    assert DEFAULT_BUCKETS == (1, 4, 16, 64)
    assert _build_serving_executor("", "", "").buckets == DEFAULT_BUCKETS
    assert _build_serving_executor("", "", "1,8").buckets == (1, 8)
    args = build_parser().parse_args(
        ["bench_serve", "--requests", "10", "--concurrency", "2"])
    assert args.requests == 10


def test_bench_serve_cli_emits_json(capsys):
    import json

    from poseidon_tpu.runtime.cli import main

    assert main(["bench_serve", "--requests", "20", "--concurrency", "2",
                 "--buckets", "1,2,4", "--batch", "3"]) == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    payload = json.loads(line)
    assert payload["metric"] == "serving_p99_ms"
    assert payload["ok"] == 20 and payload["unit"] == "ms"
    assert payload["p50_ms"] is not None and payload["throughput_rps"] > 0


def test_parse_buckets():
    from poseidon_tpu.serving.executor import parse_buckets

    assert parse_buckets("1,4,16,64") == (1, 4, 16, 64)
    assert parse_buckets("8,2") == (2, 8)
    assert parse_buckets("") == (1, 4, 16, 64)     # an unset --buckets
    with pytest.raises(ValueError):
        parse_buckets("0,2")
    with pytest.raises(ValueError):
        parse_buckets("a,b")
