"""Threaded socket front-end for the serving tier.

Reuses the proto/wire.py length-prefixed framing and the malformed-frame
containment pattern from the async-SSP ParamService: a corrupt peer (torn
frame, garbage header, undecodable payload) gets ITS connection logged and
dropped; everyone else keeps being served. The accept loop and per-request
handling are thread-per-connection — request concurrency is what feeds the
micro-batcher.

Request protocol (pickled dicts, one frame per message):

- ``{"kind": "infer", "inputs": {name: ndarray}, "deadline_ms": float?}``
  -> ``{"ok": True, "outputs": {...}}`` on success;
  -> ``{"ok": False, "shed": True, "error": ...}`` under backpressure
  (bounded queue full, or shutting down) — explicit, immediate;
  -> ``{"ok": False, "deadline_exceeded": True, "error": ...}`` when the
  per-request deadline expired in queue;
  -> ``{"ok": False, "error": ...}`` on malformed inputs.
- ``{"kind": "generate", "inputs": {"prompt": 1-D int array, "max_new":
  int?, "eos_id": int?}, "deadline_ms": float?, "stream": bool?}`` — LLM
  decode through the continuous-batching scheduler (serving/continuous.py).
  Same reply shapes as ``infer`` (``outputs`` = tokens/n_new/prompt_len);
  with ``stream`` the reply frame is preceded by zero or more
  ``{"kind": "gen_chunk", "tokens": [...]}`` frames carrying the
  CUMULATIVE generated tokens (cumulative so a reconnect-resend or a
  failover re-prefill restarts the stream without loss).
- ``{"kind": "stats"}`` -> latency percentiles, queue depth, batch-fill
  ratio, shed count, reload count (the `/stats`-style introspection op).
- ``{"kind": "reload"}`` -> force one hot-reload poll now (when a
  reloader is attached); returns what it found.
- ``{"kind": "health"}`` -> ``{"ok": True, "draining": bool}``.
- ``{"kind": "bye"}`` -> close this connection.

Shutdown (the SIGTERM/SIGINT path): ``shutdown()`` stops accepting new
connections, lets the batcher drain every admitted request, answers the
in-flight replies, then closes. No admitted request is silently dropped.
"""

from __future__ import annotations

import socket
import threading
import time
from typing import Dict, Optional

import numpy as np

from ..proto.wire import (WIRE_CODEC_VERSION, FrameError, mark_codec_socket,
                          recv_frame, send_frame, wire_codec_enabled)
from ..runtime.metrics import StatsRegistry, log
from .batcher import DeadlineError, DynamicBatcher, ShedError

__all__ = ["InferenceServer"]


class InferenceServer:
    """Serve a :class:`BucketedExecutor` — or a whole
    :class:`~poseidon_tpu.serving.fleet.ReplicaManager` — over TCP
    (port 0 = ephemeral).

    Exactly one of ``executor`` / ``fleet`` must be given. The executor
    form is the PR-2 single-engine path (one private micro-batcher built
    from ``max_delay_s``/``max_queue``); the fleet form routes every
    request through the manager's least-loaded router instead — there the
    batching/admission knobs live on each REPLICA's batcher (configured
    when the fleet was built) and this constructor's ``max_delay_s``/
    ``max_queue`` are unused — and the `stats` op becomes the fleet
    health surface (per-replica rows). ``stats_refresh_s > 0`` refreshes
    the StatsRegistry "serving" section on a timer so a live metrics
    endpoint shows health without anyone calling the stats op."""

    def __init__(self, executor=None, host: str = "127.0.0.1", port: int = 0,
                 max_delay_s: float = 0.005, max_queue: int = 64,
                 default_deadline_s: Optional[float] = None,
                 reloader=None, stats: Optional[StatsRegistry] = None,
                 fleet=None, stats_refresh_s: float = 0.0):
        if (executor is None) == (fleet is None):
            raise ValueError("pass exactly one of executor= or fleet=")
        self.executor = executor
        self.fleet = fleet
        self.reloader = reloader
        self.stats = stats or StatsRegistry()
        self.default_deadline_s = default_deadline_s
        # an executor that brings its own scheduler (GenerateExecutor ->
        # ContinuousScheduler) plugs in here, same hook as
        # fleet.Replica._attach_batcher
        mk = (getattr(executor, "make_batcher", None)
              if executor is not None else None)
        self.batcher = (None if fleet is not None else
                        mk(max_delay_s=max_delay_s, max_queue=max_queue)
                        if mk is not None else
                        DynamicBatcher(executor, max_delay_s=max_delay_s,
                                       max_queue=max_queue))
        self.bad_frames = 0
        self.server_errors = 0
        self.connections = 0
        self._active_replies = 0   # requests received, reply not yet sent
        self.draining = False
        self._stop = threading.Event()
        self._done = threading.Event()     # fully shut down
        self._shutting_down = False
        self._lock = threading.Lock()
        self._srv = socket.create_server((host, port))
        # set here, not on the accept thread: a server shut down before
        # that thread first runs would have it touch a closed socket
        self._srv.settimeout(0.25)
        self.host = host
        self.port = self._srv.getsockname()[1]
        self.addr = (host, self.port)
        self._accept_thread = threading.Thread(target=self._accept_loop,
                                               daemon=True)
        self._accept_thread.start()
        self._started = time.time()
        self._stats_refresh_s = float(stats_refresh_s)
        if self._stats_refresh_s > 0:
            threading.Thread(target=self._stats_refresh_loop,
                             daemon=True).start()

    def _stats_refresh_loop(self) -> None:
        """Keep the StatsRegistry "serving" section current for the live
        metrics endpoint — fleet health must be visible without a client
        calling the stats op."""
        while not self._stop.wait(self._stats_refresh_s):
            try:
                self.stats_snapshot()
            except Exception:  # noqa: BLE001 — telemetry never kills serving
                pass

    # ---- accept/handle --------------------------------------------------- #
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            with self._lock:
                self.connections += 1
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            while not self._done.is_set():
                try:
                    msg = recv_frame(conn)
                except FrameError as e:
                    # containment: a corrupt peer loses ITS connection; the
                    # server keeps serving everyone else
                    with self._lock:
                        self.bad_frames += 1
                    log(f"serving: dropping connection on bad frame: {e}")
                    return
                except (ConnectionError, EOFError, OSError):
                    return
                # a received request is owed a reply: the counter keeps
                # shutdown() from declaring the server down between a
                # drained batch completing and its replies hitting the wire
                with self._lock:
                    self._active_replies += 1
                try:
                    try:
                        reply = self._dispatch(msg, conn)
                    except (ConnectionError, OSError):
                        return
                    except (KeyError, TypeError, ValueError) as e:
                        # bad request SHAPE (missing kind/fields, wrong
                        # types): same containment as a torn frame, but the
                        # channel is intact — tell the client
                        with self._lock:
                            self.bad_frames += 1
                        reply = {"ok": False,
                                 "error": f"{type(e).__name__}: {e}"}
                    except Exception as e:  # noqa: BLE001 — OUR failure
                        # server-side failure (executor/XLA/reloader): never
                        # billed to the client as a bad frame
                        with self._lock:
                            self.server_errors += 1
                        log(f"serving: internal error: "
                            f"{type(e).__name__}: {e}")
                        reply = {"ok": False, "server_error": True,
                                 "error": f"{type(e).__name__}: {e}"}
                    if reply is None:       # bye
                        return
                    try:
                        send_frame(conn, reply)
                    except (ConnectionError, OSError):
                        return
                finally:
                    with self._lock:
                        self._active_replies -= 1
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _dispatch(self, msg: Dict, conn=None) -> Optional[Dict]:
        kind = msg["kind"]
        if kind == "wire":
            # binary tensor codec negotiation: affirm iff the client
            # speaks exactly our version and the codec is enabled; the
            # infer/generate tensor payloads on this connection then skip
            # pickle entirely. An old client never sends this kind; an
            # old server answers it {"ok": False, "error": ...} through
            # the unknown-kind path — the client stays on pickle.
            ok = bool(wire_codec_enabled()
                      and msg.get("codec") == WIRE_CODEC_VERSION)
            if ok and conn is not None:
                mark_codec_socket(conn)
            return {"ok": ok, "codec": WIRE_CODEC_VERSION}
        if kind == "infer":
            return self._handle_infer(msg)
        if kind == "generate":
            return self._handle_generate(msg, conn)
        if kind == "stats":
            return {"ok": True, "stats": self.stats_snapshot()}
        if kind == "health":
            if self.fleet is not None:
                return {"ok": True, "draining": self.draining,
                        "states": self.fleet.state_counts(),
                        "reload_generation": self.fleet.reload_generation}
            return {"ok": True, "draining": self.draining,
                    "params_version": self.executor.params_version}
        if kind == "reload":
            if self.reloader is None:
                return {"ok": False, "error": "no reloader attached"}
            reloaded = self.reloader.check_now()
            reply = {"ok": True, "reloaded": reloaded,
                     "path": self.reloader.current_path,
                     "last_error": self.reloader.last_error}
            if self.fleet is not None:
                reply["reload_generation"] = self.fleet.reload_generation
            else:
                reply["params_version"] = self.executor.params_version
            return reply
        if kind == "bye":
            return None
        raise ValueError(f"unknown request kind {kind!r}")

    def _handle_infer(self, msg: Dict) -> Dict:
        deadline_ms = msg.get("deadline_ms")
        deadline_s = (float(deadline_ms) / 1e3 if deadline_ms is not None
                      else self.default_deadline_s)
        try:
            if self.fleet is not None:
                outputs, rep = self.fleet.submit(msg["inputs"],
                                                 deadline_s=deadline_s)
                return {"ok": True, "outputs": outputs,
                        "replica": rep.index,
                        "params_version": rep.executor.params_version}
            outputs = self.batcher.submit(msg["inputs"],
                                          deadline_s=deadline_s)
            return {"ok": True, "outputs": outputs,
                    "params_version": self.executor.params_version}
        except ShedError as e:
            return {"ok": False, "shed": True, "error": str(e)}
        except DeadlineError as e:
            return {"ok": False, "deadline_exceeded": True, "error": str(e)}
        except (ValueError, TimeoutError) as e:
            return {"ok": False, "error": f"{type(e).__name__}: {e}"}

    def _handle_generate(self, msg: Dict, conn=None) -> Dict:
        """LLM decode: same admission/deadline error surface as ``infer``;
        the batcher behind it is a ContinuousScheduler, so the request is
        a SEQUENCE (admitted/retired per decode step), not a dispatch.

        Streaming rides the scheduler's per-token callback: each chunk
        frame carries the cumulative tokens so far, written from the
        scheduler thread while this handler thread blocks in submit (the
        final reply only goes out after the last chunk). A broken chunk
        send kills the stream, never the sequence or the loop."""
        deadline_ms = msg.get("deadline_ms")
        deadline_s = (float(deadline_ms) / 1e3 if deadline_ms is not None
                      else self.default_deadline_s)
        inputs = dict(msg["inputs"])
        if msg.get("stream") and conn is not None:
            def emit(tokens, _conn=conn):
                # int32 buffer, not a list of ints: on a codec-negotiated
                # connection the cumulative token chunk travels as one
                # raw tensor buffer (the client converts back to ints)
                send_frame(_conn, {"kind": "gen_chunk",
                                   "tokens": np.asarray(tokens, np.int32)})
            inputs["stream"] = emit
        try:
            if self.fleet is not None:
                outputs, rep = self.fleet.submit(inputs,
                                                 deadline_s=deadline_s)
                return {"ok": True, "outputs": outputs,
                        "replica": rep.index,
                        "params_version": rep.executor.params_version}
            outputs = self.batcher.submit(inputs, deadline_s=deadline_s)
            return {"ok": True, "outputs": outputs,
                    "params_version": self.executor.params_version}
        except ShedError as e:
            return {"ok": False, "shed": True, "error": str(e)}
        except DeadlineError as e:
            return {"ok": False, "deadline_exceeded": True, "error": str(e)}
        except (ValueError, TimeoutError) as e:
            return {"ok": False, "error": f"{type(e).__name__}: {e}"}

    # ---- introspection ---------------------------------------------------- #
    def stats_snapshot(self) -> Dict:
        """The `/stats` payload: p50/p99 request latency, queue depth,
        batch-fill ratio, shed count — registered as a StatsRegistry
        section too, so a run-level stats.yaml dump carries it. With a
        fleet, the payload is the manager's aggregate plus one row per
        replica (state, queue depth, batch fill, sheds, reload
        generation) — the fleet health surface."""
        if self.fleet is not None:
            snap = self.fleet.stats_snapshot()
            snap.update({
                "bad_frames": self.bad_frames,
                "server_errors": self.server_errors,
                "connections": self.connections,
                "uptime_s": round(time.time() - self._started, 3),
                "draining": self.draining,
                "reloads": (0 if self.reloader is None
                            else self.reloader.reloads),
                "reloader": (None if self.reloader is None else {
                    "reloads": self.reloader.reloads,
                    "failed_reloads": self.reloader.failed_reloads,
                    "last_error": self.reloader.last_error,
                    "current_path": self.reloader.current_path,
                }),
            })
            self.stats.set_section("serving", snap)
            return snap
        b = self.batcher
        fill = b.fill_ratio()
        snap = {
            "latency": b.latency.summary(),
            "queue_depth": b.queue_depth,
            "max_queue": b.max_queue,
            "batches": b.batches,
            "batched_rows": b.batched_rows,
            "batch_fill": None if fill is None else round(fill, 4),
            "shed": b.shed_count,
            "deadline_expired": b.deadline_expired,
            "bad_frames": self.bad_frames,
            "server_errors": self.server_errors,
            "connections": self.connections,
            "rows_served": self.executor.rows_served,
            # CNN-executor-only telemetry; a GenerateExecutor reports its
            # paged/decode counters through the batcher snapshot instead
            "rows_padded": getattr(self.executor, "rows_padded", 0),
            "bucket_calls": dict(getattr(self.executor, "calls", {})),
            # per-rung fill: which compile slots dispatch real rows vs
            # padding (capacity signal for re-cutting the bucket ladder);
            # getattr: duck-typed test executors need not implement it
            "executor_bucket_fill": getattr(self.executor, "bucket_fill",
                                            lambda: None)(),
            "params_version": self.executor.params_version,
            "reloads": (0 if self.reloader is None
                        else self.reloader.reloads),
            # the reloader's full swap telemetry (hot-reload health must
            # be visible from the stats op, not only the server log)
            "reloader": (None if self.reloader is None else {
                "reloads": self.reloader.reloads,
                "failed_reloads": self.reloader.failed_reloads,
                "last_error": self.reloader.last_error,
                "current_path": self.reloader.current_path,
            }),
            "uptime_s": round(time.time() - self._started, 3),
            "draining": self.draining,
        }
        self.stats.set_section("serving", snap)
        return snap

    # ---- shutdown --------------------------------------------------------- #
    def request_stop(self) -> None:
        """Async-signal-safe stop request: flip the flags only (a signal
        handler must not join threads). The thread blocked in
        ``wait_until_stopped`` then runs the actual ``shutdown``."""
        self.draining = True
        self._stop.set()

    def wait_until_stopped(self, poll_s: float = 0.25) -> None:
        while not self._stop.wait(poll_s):
            pass

    def shutdown(self, drain: bool = True, timeout_s: float = 30.0) -> None:
        """Graceful stop: refuse new connections, drain the admitted
        queue (every in-flight request gets its reply), then close.
        Idempotent; safe to call after ``request_stop``."""
        with self._lock:
            already = self._shutting_down
            self._shutting_down = True
        if already:
            self._done.wait(timeout=timeout_s)
            return
        self.draining = True
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        if self.reloader is not None:
            self.reloader.close()
        # drain: every admitted request completes and its handler thread
        # writes the reply before we declare the server down
        if self.fleet is not None:
            self.fleet.shutdown(drain=drain, timeout_s=timeout_s)
        else:
            self.batcher.close(drain=drain, timeout_s=timeout_s)
        # the batcher completing a request only SETS its event; the handler
        # thread still has to wake and write the reply frame — wait for
        # every received-but-unreplied request to hit the wire, or the
        # process exit right after shutdown() would kill the daemon
        # handlers mid-reply (a silently dropped request)
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            with self._lock:
                if self._active_replies <= 0:
                    break
            time.sleep(0.005)
        self._done.set()

    def close(self) -> None:
        self.shutdown()
