"""Metrics registry: the analog of the reference's net-output PS tables + stats.

The reference aggregates per-display-window training metrics into a PS table
whose rows are {iter, time, loss, outputs...} and dumps an averaged CSV at the
end of training (``PrintNetOutputs``, solver.cpp:699-756), plus a YAML stats
artifact when compiled with -DPETUUM_STATS (stats.hpp). Here metrics come back
from the compiled step already cross-replica-averaged; this module accumulates
them per display window and writes the same artifact shapes (CSV + YAML).
"""

from __future__ import annotations

import os
import threading
import time
from collections import defaultdict, deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from .spans import recorder as _spans


class MetricsTable:
    def __init__(self, name: str):
        self.name = name
        self.rows: List[Dict[str, float]] = []
        self._window: Dict[str, List[float]] = defaultdict(list)
        self._t0 = time.time()

    def accumulate(self, metrics: Dict[str, float]) -> None:
        for k, v in metrics.items():
            self._window[k].append(float(v))

    def flush_row(self, iteration: int) -> Dict[str, float]:
        row = {"iter": iteration, "time": round(time.time() - self._t0, 3)}
        for k, vals in self._window.items():
            row[k] = sum(vals) / max(len(vals), 1)
        self._window.clear()
        self.rows.append(row)
        return row

    def to_csv(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        cols: List[str] = []
        for row in self.rows:
            for k in row:
                if k not in cols:
                    cols.append(k)
        with open(path, "w") as f:
            f.write(",".join(cols) + "\n")
            for row in self.rows:
                f.write(",".join(str(row.get(c, "")) for c in cols) + "\n")


class StatsRegistry:
    """Run-level counters/timers dumped as one YAML per run (stats.hpp analog).

    ``set_section`` attaches a nested dict (e.g. the static per-layer comm
    accounting from comm_stats.py — the analog of the reference's bg oplog
    bytes / server push bytes stats). Thread-safe: the engine loop, span
    instrumentation, serving handler threads and the live metrics endpoint
    (:class:`MetricsServer`) all touch one registry concurrently.

    The YAML dump is atomic (tmp + rename) and the engine calls it at
    every display boundary — a crashed or preempted run keeps its
    telemetry up to the last boundary, with only sweepable tmp litter."""

    def __init__(self):
        self.counters: Dict[str, float] = defaultdict(float)
        self.timers: Dict[str, float] = defaultdict(float)
        self.gauges: Dict[str, float] = {}
        self.sections: Dict[str, dict] = {}
        self._snapshot_only: Dict[str, frozenset] = {}
        self._lock = threading.Lock()

    def add(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self.counters[name] += value

    def add_time(self, name: str, seconds: float) -> None:
        with self._lock:
            self.timers[name] += seconds

    def set_gauge(self, name: str, value: float) -> None:
        """Last-value-wins instantaneous reading (iteration, loss, queue
        depth) — the live-endpoint counterpart of a monotonic counter."""
        with self._lock:
            self.gauges[name] = value

    def set_section(self, name: str, data: dict,
                    snapshot_only: Tuple[str, ...] = ()) -> None:
        """``snapshot_only`` names keys of ``data`` that ``snapshot()``
        hands out and the rendered documents (stats.yaml, the metrics
        endpoint) leave out: bulk that a program reads and a person does
        not (the step's instruction -> scope map), kept out of the file
        that is rewritten at every display boundary."""
        with self._lock:
            self.sections[name] = data
            self._snapshot_only[name] = frozenset(snapshot_only)

    def snapshot(self, rendered: bool = False) -> Dict[str, dict]:
        """A consistent copy of everything (one lock hold); with
        ``rendered``, less the sections' ``snapshot_only`` keys."""
        with self._lock:
            skip = self._snapshot_only if rendered else {}
            return {"counters": dict(self.counters),
                    "timers_sec": {k: round(v, 6)
                                   for k, v in self.timers.items()},
                    "gauges": dict(self.gauges),
                    "sections": {
                        name: {k: v for k, v in sec.items()
                               if k not in skip.get(name, ())}
                        for name, sec in self.sections.items()}}

    def render_text(self) -> str:
        """Flat ``key=value`` lines — what ``--metrics_port`` serves (one
        curl mid-run answers "where is this job"). Sections flatten with
        dotted keys; non-scalar leaves are skipped (the YAML has them)."""
        snap = self.snapshot(rendered=True)
        lines = []

        def emit(prefix: str, tree: dict) -> None:
            for k in sorted(tree):
                v = tree[k]
                if isinstance(v, dict):
                    emit(f"{prefix}{k}.", v)
                elif isinstance(v, (int, float)) and not isinstance(v, bool):
                    lines.append(f"{prefix}{k}={v}")

        emit("", snap["counters"])
        emit("", snap["gauges"])
        emit("", {f"{k}_sec": v for k, v in snap["timers_sec"].items()})
        emit("", snap["sections"])
        return "\n".join(lines) + "\n"

    @staticmethod
    def _write_tree(f, tree: dict, indent: int) -> None:
        pad = "  " * indent
        for k, v in tree.items():
            if isinstance(v, dict):
                f.write(f"{pad}{k}:\n")
                StatsRegistry._write_tree(f, v, indent + 1)
            else:
                f.write(f"{pad}{k}: {'null' if v is None else v}\n")

    def render_yaml(self) -> str:
        """The full stats.yaml document as a string — ONE renderer shared
        by ``dump_yaml`` and the live ``/yaml`` endpoint, so the two can
        never drift."""
        import io
        snap = self.snapshot(rendered=True)
        f = io.StringIO()
        f.write("counters:\n")
        for k in sorted(snap["counters"]):
            f.write(f"  {k}: {snap['counters'][k]}\n")
        f.write("timers_sec:\n")
        for k in sorted(snap["timers_sec"]):
            f.write(f"  {k}: {snap['timers_sec'][k]}\n")
        if snap["gauges"]:
            f.write("gauges:\n")
            for k in sorted(snap["gauges"]):
                f.write(f"  {k}: {snap['gauges'][k]}\n")
        for name in sorted(snap["sections"]):
            f.write(f"{name}:\n")
            self._write_tree(f, snap["sections"][name], 1)
        return f.getvalue()

    def dump_yaml(self, path: str) -> None:
        """Atomic write (tmp + os.replace): a reader — or the next run's
        auto-resume forensics — never sees a torn stats.yaml, and a
        killed writer leaves only a sweepable ``.tmp.<pid>`` file."""
        doc = self.render_yaml()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(doc)
        os.replace(tmp, path)


def read_stats_yaml(path: str) -> Dict[str, dict]:
    """Read back a document :meth:`StatsRegistry.render_yaml` wrote: nested
    dicts by two-space indent, every leaf kept as the string it was
    written as (``"null"`` included). The inverse of ``_write_tree`` and
    nothing more — not a YAML parser."""
    root: Dict[str, dict] = {}
    stack = [(-1, root)]
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            indent = len(line) - len(line.lstrip(" "))
            key, _, val = line.strip().partition(": ")
            while stack[-1][0] >= indent:
                stack.pop()
            if not val and key.endswith(":"):
                child: Dict[str, dict] = {}
                stack[-1][1][key[:-1]] = child
                stack.append((indent, child))
            else:
                stack[-1][1][key] = val
    return root


class MetricsServer:
    """The ``--metrics_port`` one-liner: a read-only HTTP endpoint serving
    a StatsRegistry as ``text/plain`` key=value lines, curl-able mid-run.

    GET /        -> flat key=value (render_text)
    GET /yaml    -> the stats.yaml document, rendered live

    Runs a daemon-threaded stdlib HTTP server; ``port=0`` binds an
    ephemeral port (read it back from ``.port`` — the tests do). Strictly
    read-only: no mutation op exists, so exposing it on loopback during a
    long run costs nothing but a socket."""

    def __init__(self, registry: "StatsRegistry", port: int = 0,
                 host: str = "127.0.0.1"):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        reg = registry

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):          # noqa: N802 — stdlib contract
                if self.path.rstrip("/") == "/yaml":
                    body = reg.render_yaml().encode()
                else:
                    body = reg.render_text().encode()
                try:
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                except OSError:
                    # client went away / endpoint closing mid-reply: a
                    # read-only metrics poll is never worth a stack trace
                    pass

            def log_message(self, *args):  # quiet: not request-log noise
                pass

        self._srv = ThreadingHTTPServer((host, port), Handler)
        self._srv.daemon_threads = True
        self.host = host
        self.port = self._srv.server_address[1]
        self._thread = threading.Thread(target=self._srv.serve_forever,
                                        name="metrics_server", daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._srv.shutdown()
        self._srv.server_close()
        self._thread.join(timeout=5.0)


def scalar_rows(metrics: Dict) -> List[Dict[str, float]]:  # static-ok: JIT102
    """Materialize one dispatch's device metrics into float rows, one per
    optimizer step. Single-step dispatches hold scalars (one row);
    scan-chunk dispatches hold [K]-stacked arrays (K rows). ``np.asarray``
    on a device value blocks until the step that produced it has run —
    this is where the pipeline actually waits on the device."""
    arrs = {k: np.asarray(v) for k, v in metrics.items()}
    k_steps = max((a.shape[0] for a in arrs.values() if a.ndim >= 1),
                  default=1)
    if k_steps == 1 and all(a.ndim == 0 for a in arrs.values()):
        return [{k: float(a) for k, a in arrs.items()}]
    return [{k: float(a[i]) if a.ndim >= 1 else float(a)
             for k, a in arrs.items()} for i in range(k_steps)]


class AsyncScalarFetcher:
    """Bounded in-flight dispatch window + off-thread scalar drain.

    The training loop dispatches step k+1 BEFORE step k's metrics are
    read: each dispatch's device metrics are ``put()`` here, a drainer
    thread materializes them to host floats (blocking on the device off
    the train thread), and ``put`` itself blocks only when more than
    ``max_in_flight`` dispatches are un-materialized — that backpressure
    IS the dispatch window. ``sync()`` is the hard host<->device sync
    point (test/snapshot boundaries and end of training; a display
    boundary is shown from the rows as they drain and syncs nothing).

    NaN/divergence detection rides the drain: the first non-finite value
    of a watched key records ``(iteration, key, value)`` in
    ``divergence``, observed by the loop at most ``max_in_flight`` steps
    after the step that produced it (the pipelining lag). Rows come back
    in dispatch order, tagged with their first iteration."""

    def __init__(self, max_in_flight: int = 2,
                 watch_keys: Tuple[str, ...] = ("loss",)):
        self.max_in_flight = max(1, int(max_in_flight))
        self.watch_keys = tuple(watch_keys)
        self.divergence: Optional[Tuple[int, str, float]] = None
        self._cond = threading.Condition()
        self._inbox: deque = deque()   # (first_iter, device metrics)
        self._drained: deque = deque()  # (iter, float row)
        self._pending = 0               # dispatches not yet materialized
        self._error: Optional[Exception] = None
        self._closed = False
        self._thread = threading.Thread(target=self._drain_loop,
                                        name="drainer", daemon=True)
        self._thread.start()

    @staticmethod
    def _already_ready(metrics: Dict) -> bool:
        """True when every value's device computation has finished
        (np/host scalars count as ready) — nothing left to overlap."""
        for v in metrics.values():
            is_ready = getattr(v, "is_ready", None)
            if is_ready is not None and not is_ready():
                return False
        return True

    # ---- producer side (the train thread) ---------------------------- #
    def put(self, first_iter: int, metrics: Dict) -> None:
        """Enqueue one dispatch's device metrics (first_iter = the global
        iteration of its first optimizer step), then block until the
        window INCLUDING this entry has room for the caller's next
        dispatch: on return at most ``max_in_flight - 1`` dispatches are
        un-materialized, so the step the loop dispatches next brings the
        in-flight count to at most ``max_in_flight``. With
        ``max_in_flight=1`` this drains the entry itself before returning
        — the genuinely serial loop.

        Fast path: when the window is empty and the dispatch has ALREADY
        finished (CPU's effectively-synchronous dispatch, or a device
        that ran ahead of the host), the scalars materialize inline with
        zero thread handoff — the drainer ping-pong is a measured
        ~0.4 ms/step tax on a 2-core host, and there is nothing left to
        overlap for a finished dispatch. Accelerator dispatches that are
        still running take the drainer path and overlap for real."""
        with self._cond:
            if self._error:
                raise self._error
            inline = (self._pending == 0 and not self._inbox
                      and self._already_ready(metrics))
            if not inline:
                self._pending += 1
                self._inbox.append((first_iter, metrics))
                self._cond.notify_all()
                while self._pending > self.max_in_flight - 1 and \
                        not self._error:
                    self._cond.wait()
                if self._error:
                    raise self._error
                return
        # materialize OUTSIDE the lock (values are ready, so this cannot
        # block on the device); the single-producer contract means no
        # other put can interleave, and the drainer's inbox is empty, so
        # row order is preserved
        rows = scalar_rows(metrics)
        with self._cond:
            self._ingest(first_iter, rows)

    def take_drained(self) -> List[Tuple[int, Dict[str, float]]]:
        """Rows materialized so far, in order, without waiting."""
        with self._cond:
            out = list(self._drained)
            self._drained.clear()
        return out

    def sync(self) -> List[Tuple[int, Dict[str, float]]]:
        """Hard sync: wait until every pending dispatch has materialized,
        then return all drained rows (in order). Re-raises a drainer
        failure."""
        with self._cond:
            while self._pending and not self._error:
                self._cond.wait()
            if self._error:
                raise self._error
            out = list(self._drained)
            self._drained.clear()
        return out

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=5.0)

    def _ingest(self, first_iter: int, rows) -> None:
        """Append materialized rows + run the divergence watch. Caller
        holds the lock. This is the moment a step's metrics are host
        floats — on the drainer's thread, or on the train thread when the
        dispatch had already finished — and the ``step_done`` instant
        marks it on the span timeline (the steps of one scan-chunk dispatch
        complete together and say so: ``dispatch`` = their first)."""
        for i, row in enumerate(rows):
            it = first_iter + i
            if _spans.enabled:
                _spans.instant(
                    "step_done", "step",
                    {"iter": it} if len(rows) == 1
                    else {"iter": it, "dispatch": first_iter})
            self._drained.append((it, row))
            if self.divergence is None:
                for k in self.watch_keys:
                    v = row.get(k)
                    if v is not None and not np.isfinite(v):
                        self.divergence = (it, k, v)
                        break

    # ---- drainer thread ---------------------------------------------- #
    def _drain_loop(self) -> None:
        while True:
            with self._cond:
                while not self._inbox and not self._closed:
                    self._cond.wait()
                if not self._inbox and self._closed:
                    return
                first_iter, metrics = self._inbox.popleft()
            try:
                rows = scalar_rows(metrics)
            except Exception as e:  # noqa: BLE001 — surface, never wedge
                with self._cond:
                    self._error = e
                    self._pending = 0
                    self._cond.notify_all()
                return
            with self._cond:
                self._ingest(first_iter, rows)
                self._pending -= 1
                self._cond.notify_all()


class LatencyWindow:
    """Sliding-window latency percentiles for the serving tier.

    A bounded deque of the last ``maxlen`` samples (seconds): O(1) record
    on the hot path, sort-on-read only when someone asks for a summary —
    the `/stats` op, not the request path. Thread-safe (server handler
    threads record concurrently)."""

    def __init__(self, maxlen: int = 2048):
        self._samples: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self.count = 0            # total ever recorded (window is bounded)

    def record(self, seconds: float) -> None:
        with self._lock:
            self._samples.append(float(seconds))
            self.count += 1

    @staticmethod
    def _rank(data: List[float], q: float) -> float:
        """Nearest-rank percentile over sorted ``data`` (one formula, used
        by percentile() and summary() alike)."""
        return data[max(0, min(len(data) - 1,
                               int(round(q / 100.0 * (len(data) - 1)))))]

    def percentile(self, q: float) -> Optional[float]:
        """Nearest-rank percentile (q in [0, 100]) over the window, in
        seconds; None while empty."""
        with self._lock:
            data = sorted(self._samples)
        return self._rank(data, q) if data else None

    def summary(self) -> Dict[str, float]:
        """{count, p50_ms, p99_ms, mean_ms} over the window (empty -> just
        count=0) — the serving `/stats` payload shape."""
        with self._lock:
            data = sorted(self._samples)
        if not data:
            return {"count": 0}
        return {
            "count": self.count,
            "p50_ms": round(self._rank(data, 50.0) * 1e3, 3),
            "p99_ms": round(self._rank(data, 99.0) * 1e3, 3),
            "mean_ms": round(sum(data) / len(data) * 1e3, 3),
        }

    def samples(self) -> List[float]:
        """A copy of the current window (seconds) — merge fodder."""
        with self._lock:
            return list(self._samples)

    @classmethod
    def merged_summary(cls, windows) -> Dict[str, float]:
        """One summary over the POOLED samples of many windows (the fleet
        aggregation: per-replica percentiles do not average, so the fleet
        row re-ranks the union instead). Counts sum over lifetimes; the
        percentile pool is bounded by each window's maxlen."""
        data: List[float] = []
        total = 0
        for w in windows:
            data.extend(w.samples())
            total += w.count
        if not data:
            return {"count": 0}
        data.sort()
        return {
            "count": total,
            "p50_ms": round(cls._rank(data, 50.0) * 1e3, 3),
            "p99_ms": round(cls._rank(data, 99.0) * 1e3, 3),
            "mean_ms": round(sum(data) / len(data) * 1e3, 3),
        }


def log(msg: str, *, rank: int = 0) -> None:
    """Rank-0-only progress logging, the reference's client0/thread0 idiom."""
    if rank == 0:
        print(msg, flush=True)
