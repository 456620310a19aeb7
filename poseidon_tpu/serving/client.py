"""Blocking serving client + the load generator shared by bench and tests.

Transport recovery rides ``runtime/retry.retry_with_backoff`` (capped
exponential backoff, full jitter — the same policy the async-SSP client
uses): a connection that dies mid-request is redialed and the request
RESENT, which is safe because ``infer`` is read-only/idempotent — the
kill-mid-request chaos test pins exactly this path. Application-level
refusals are NOT retried here: a shed response is the server's explicit
backpressure signal and surfaces to the caller as :class:`ServingError`
with ``shed=True`` — retrying into a full queue is the caller's policy
decision, not the transport's.
"""

from __future__ import annotations

import random
import socket
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..proto.wire import (WIRE_CODEC_VERSION, mark_codec_socket, recv_frame,
                          send_frame, wire_codec_enabled)
from ..runtime.metrics import LatencyWindow
from ..runtime.retry import retry_with_backoff

__all__ = ["ServingClient", "ServingError", "run_load"]


class ServingError(RuntimeError):
    """A structured refusal from the server (shed / deadline / bad
    request). ``shed`` and ``deadline_exceeded`` mirror the reply flags."""

    def __init__(self, message: str, *, shed: bool = False,
                 deadline_exceeded: bool = False):
        super().__init__(message)
        self.shed = shed
        self.deadline_exceeded = deadline_exceeded


class ServingClient:
    """One connection, blocking RPCs, transparent reconnect-and-resend."""

    def __init__(self, addr: Tuple[str, int], connect_deadline_s: float = 10.0,
                 retry_deadline_s: float = 10.0,
                 backoff_base_s: float = 0.02, backoff_cap_s: float = 0.5,
                 rng: Optional[random.Random] = None):
        self.addr = tuple(addr)
        self.retry_deadline_s = retry_deadline_s
        self.backoff_base_s = backoff_base_s
        self.backoff_cap_s = backoff_cap_s
        self._rng = rng or random.Random()
        self.reconnects = 0
        self._sock = retry_with_backoff(
            self._dial, deadline=connect_deadline_s, base=backoff_base_s,
            cap=backoff_cap_s, rng=self._rng, retry_on=(OSError, EOFError))

    def _dial(self) -> socket.socket:
        sk = socket.create_connection(self.addr, timeout=5.0)
        # binary tensor codec negotiation (re-run per dial — marking is
        # per socket). The offer itself is pickle; an old server answers
        # {"ok": False} through its unknown-kind path and this client
        # simply stays on the pickle wire — same frames as today.
        if wire_codec_enabled():
            try:
                send_frame(sk, {"kind": "wire",
                                "codec": WIRE_CODEC_VERSION}, codec=False)
                ack = recv_frame(sk)
                if isinstance(ack, dict) and ack.get("ok") \
                        and ack.get("codec") == WIRE_CODEC_VERSION:
                    mark_codec_socket(sk)
            except BaseException:
                sk.close()
                raise
        sk.settimeout(None)   # established: block (slow != dead)
        return sk

    def _rpc(self, msg: Dict) -> Dict:
        try:
            send_frame(self._sock, msg)
            return recv_frame(self._sock)
        except (OSError, EOFError) as e:
            # dead channel mid-request: redial and RESEND (idempotent ops
            # only ride this client), with backoff + jitter
            first_err = e

        def attempt() -> Dict:
            sk = self._dial()
            try:
                send_frame(sk, msg)
                out = recv_frame(sk)
            except BaseException:
                sk.close()
                raise
            old, self._sock = self._sock, sk
            try:
                old.close()
            except OSError:
                pass
            return out

        try:
            reply = retry_with_backoff(
                attempt, deadline=self.retry_deadline_s,
                base=self.backoff_base_s, cap=self.backoff_cap_s,
                rng=self._rng, retry_on=(OSError, EOFError))
        except (OSError, EOFError) as e:
            raise ConnectionError(
                f"server unreachable after {self.retry_deadline_s}s "
                f"(first error: {type(first_err).__name__}: {first_err})"
            ) from e
        self.reconnects += 1
        return reply

    def _stream_rpc(self, msg: Dict, on_tokens: Callable) -> Dict:
        """Send one request and consume ``gen_chunk`` frames until the
        final reply. Reconnect-and-RESEND is still safe mid-stream: each
        chunk carries the CUMULATIVE tokens, so a restarted generation
        just re-plays the prefix through ``on_tokens``."""
        def exchange(sock: socket.socket) -> Dict:
            send_frame(sock, msg)
            while True:
                reply = recv_frame(sock)
                if isinstance(reply, dict) and \
                        reply.get("kind") == "gen_chunk":
                    try:
                        # chunks may arrive as int32 buffers (codec wire)
                        # or lists (old servers) — callers always see ints
                        on_tokens([int(t) for t in reply["tokens"]])
                    except Exception:  # noqa: BLE001 — a broken sink must
                        pass           # not kill the stream consumption
                    continue
                return reply

        try:
            return exchange(self._sock)
        except (OSError, EOFError) as e:
            first_err = e

        def attempt() -> Dict:
            sk = self._dial()
            try:
                out = exchange(sk)
            except BaseException:
                sk.close()
                raise
            old, self._sock = self._sock, sk
            try:
                old.close()
            except OSError:
                pass
            return out

        try:
            reply = retry_with_backoff(
                attempt, deadline=self.retry_deadline_s,
                base=self.backoff_base_s, cap=self.backoff_cap_s,
                rng=self._rng, retry_on=(OSError, EOFError))
        except (OSError, EOFError) as e:
            raise ConnectionError(
                f"server unreachable after {self.retry_deadline_s}s "
                f"(first error: {type(first_err).__name__}: {first_err})"
            ) from e
        self.reconnects += 1
        return reply

    # ---- ops -------------------------------------------------------------- #
    def generate(self, prompt, max_new: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 on_tokens: Optional[Callable] = None) -> Dict:
        """LLM decode: returns ``{"tokens", "n_new", "prompt_len"}``.
        ``on_tokens`` (optional) turns on streaming — called with the
        cumulative generated-token list as decode progresses."""
        inputs: Dict = {"prompt": np.asarray(prompt, np.int32)}
        if max_new is not None:
            inputs["max_new"] = int(max_new)
        if eos_id is not None:
            inputs["eos_id"] = int(eos_id)
        msg: Dict = {"kind": "generate", "inputs": inputs}
        if deadline_ms is not None:
            msg["deadline_ms"] = float(deadline_ms)
        if on_tokens is not None:
            msg["stream"] = True
            reply = self._stream_rpc(msg, on_tokens)
        else:
            reply = self._rpc(msg)
        if not reply.get("ok"):
            raise ServingError(
                str(reply.get("error", "request refused")),
                shed=bool(reply.get("shed")),
                deadline_exceeded=bool(reply.get("deadline_exceeded")))
        return reply["outputs"]

    def infer(self, inputs: Dict[str, np.ndarray],
              deadline_ms: Optional[float] = None) -> Dict[str, np.ndarray]:
        msg: Dict = {"kind": "infer", "inputs": inputs}
        if deadline_ms is not None:
            msg["deadline_ms"] = float(deadline_ms)
        reply = self._rpc(msg)
        if not reply.get("ok"):
            raise ServingError(
                str(reply.get("error", "request refused")),
                shed=bool(reply.get("shed")),
                deadline_exceeded=bool(reply.get("deadline_exceeded")))
        return reply["outputs"]

    def stats(self) -> Dict:
        reply = self._rpc({"kind": "stats"})
        if not reply.get("ok"):
            raise ServingError(str(reply.get("error", "stats refused")))
        return reply["stats"]

    def health(self) -> Dict:
        return self._rpc({"kind": "health"})

    def reload(self) -> Dict:
        return self._rpc({"kind": "reload"})

    def close(self) -> None:
        try:
            send_frame(self._sock, {"kind": "bye"})
        except (OSError, EOFError):
            pass
        try:
            self._sock.close()
        except OSError:
            pass


# --------------------------------------------------------------------------- #
# load generator (`bench_serve` and the tests)
# --------------------------------------------------------------------------- #

def run_load(addr: Tuple[str, int],
             make_inputs: Callable[[int], Dict[str, np.ndarray]],
             n_requests: int = 200, concurrency: int = 4,
             deadline_ms: Optional[float] = None,
             retry_deadline_s: float = 10.0,
             offered_rps: Optional[float] = None,
             op: str = "infer") -> Dict:
    """Drive ``n_requests`` inferences through ``concurrency`` persistent
    client connections; returns p50/p99/goodput plus shed/error counts.

    Two load models:

    - **closed loop** (``offered_rps=None``, the default): each worker
      fires its next request the moment the previous reply lands. Load
      self-throttles to whatever the server sustains — fine for a latency
      floor, useless for a saturation curve (an overloaded server slows
      the generator down instead of being measured as overloaded).
    - **open loop** (``offered_rps=R``): request i has the fixed arrival
      time ``t0 + i/R``, independent of completions. A worker sleeps
      until its request's slot; a worker still waiting on a reply when
      its next slot passes fires late and is COUNTED (``late_fires`` —
      nonzero means concurrency is too low to realize the offered rate,
      i.e. the generator partially closed the loop). Goodput-vs-offered-
      load is measurable: offer 2x capacity and goodput saturates while
      sheds/deadlines absorb the rest.

    ``make_inputs(i)`` builds request i's input dict (vary batch sizes to
    exercise the bucket ladder). Sheds are counted, not retried — a bench
    that silently retried its way around backpressure would report a
    throughput the server cannot actually sustain.

    ``op="generate"`` drives the LLM decode op instead: ``make_inputs(i)``
    then returns ``generate`` keyword arguments (prompt/max_new/eos_id)
    and the summary gains ``tokens`` + ``goodput_tps`` (generated tokens
    per second over accepted requests — the LLM serving goodput unit)."""
    if op not in ("infer", "generate"):
        raise ValueError(f"op must be infer|generate, got {op!r}")
    if offered_rps is not None and offered_rps <= 0:
        # a zero rate would ZeroDivisionError inside every worker thread
        # (which dies silently) — refuse it loudly at the call site
        raise ValueError(f"offered_rps must be > 0, got {offered_rps}")
    lat = LatencyWindow(maxlen=max(2048, n_requests))
    counters = {"ok": 0, "shed": 0, "deadline": 0, "error": 0}
    tokens = {"v": 0}
    late = {"v": 0}
    counters_lock = threading.Lock()
    next_i = {"v": 0}
    t_start = time.monotonic()

    def worker() -> None:
        cli = ServingClient(addr, retry_deadline_s=retry_deadline_s)
        try:
            while True:
                with counters_lock:
                    i = next_i["v"]
                    if i >= n_requests:
                        return
                    next_i["v"] = i + 1
                if offered_rps is not None:
                    slot = t_start + i / offered_rps
                    lag = time.monotonic() - slot
                    if lag < 0:
                        time.sleep(-lag)
                    elif lag > 0.5 / offered_rps:
                        # past its slot by over half a period: the open
                        # loop is partially closed — count it
                        with counters_lock:
                            late["v"] += 1
                t0 = time.monotonic()
                try:
                    if op == "generate":
                        out = cli.generate(deadline_ms=deadline_ms,
                                           **make_inputs(i))
                        with counters_lock:
                            tokens["v"] += int(out.get("n_new", 0))
                    else:
                        cli.infer(make_inputs(i), deadline_ms=deadline_ms)
                    lat.record(time.monotonic() - t0)
                    key = "ok"
                except ServingError as e:
                    key = ("shed" if e.shed else
                           "deadline" if e.deadline_exceeded else "error")
                except (ConnectionError, OSError):
                    key = "error"
                with counters_lock:
                    counters[key] += 1
        finally:
            cli.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(max(1, concurrency))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = max(time.monotonic() - t_start, 1e-9)
    summary = lat.summary()
    out = {
        **counters,
        "requests": n_requests,
        "concurrency": concurrency,
        "wall_s": round(wall, 4),
        "throughput_rps": round(counters["ok"] / wall, 2),
        "goodput_rps": round(counters["ok"] / wall, 2),
        "p50_ms": summary.get("p50_ms"),
        "p99_ms": summary.get("p99_ms"),
        "mean_ms": summary.get("mean_ms"),
    }
    if op == "generate":
        out.update({
            "tokens": tokens["v"],
            "goodput_tps": round(tokens["v"] / wall, 2),
        })
    if offered_rps is not None:
        sent = sum(counters.values())
        out.update({
            "offered_rps": round(float(offered_rps), 2),
            "achieved_rps": round(sent / wall, 2),
            "late_fires": late["v"],
        })
    return out
