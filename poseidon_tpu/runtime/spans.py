"""Host-side span timeline: the telemetry spine's wall-clock half.

``jax.named_scope`` + the compiled step's scope map (runtime/attribution.py,
published by the Engine as stats section ``step_scopes``) attribute DEVICE
time; this module attributes HOST time — wherever a thread of the training
process can hold a step up. A span is a context manager around one such
region; the recorder buffers them in a bounded thread-safe deque and dumps
Chrome trace-event JSON (``chrome://tracing`` / Perfetto load it directly).

Span names, by thread (``cat`` in brackets; PERF.md section 3 names the
reader of each):

- train thread: ``prefetch_wait`` [input], ``dispatch`` with its children
  ``dispatch_rng`` and ``dispatch_execute``, ``dispatch_window`` [step],
  ``hard_sync`` [sync], ``snapshot`` [ckpt], ``telemetry_dump`` [artifact];
- reader thread (``BatchPipeline._worker``): ``producer_read``,
  ``producer_queue_full`` [input];
- prefetcher thread (``DevicePrefetcher._worker``; the train thread on the
  CPU backend's passthrough arm): ``producer_h2d``, ``producer_queue_full``;
- drainer thread (``AsyncScalarFetcher``): the instant ``step_done`` [step];
- whichever thread they happen on: ``gc_pause`` (a collector run, through
  ``gc.callbacks`` while enabled) and ``compile`` (jax's backend-compile
  event, through the Engine's listener) [runtime];
- async tier: ``async_push`` / ``async_pull`` / ``async_gate`` /
  ``async_admit`` / ``async_flush`` [async].

Spans of one step share identifiers: ``batch`` joins ``producer_read`` ->
``producer_h2d`` -> ``prefetch_wait``, ``iter`` joins ``prefetch_wait`` ->
``dispatch`` -> ``step_done``.

One clock: while the recorder is enabled and jax is already imported, every
span also enters ``jax.profiler.TraceAnnotation(name, **args)``, so under
the profiler the same spans lie in the xplane's host plane, per thread,
beside the device ops (``Engine.train`` numbers its iterations there with
``StepTraceAnnotation``). With no profiler running the annotation is a
~0.5 us no-op.

Overhead discipline: the recorder ships DISABLED. ``span()`` on a
disabled recorder returns a shared no-op context manager — one attribute
read and a call, no allocation — so instrumentation can live permanently
in the hot path (tests/test_attribution.py pins the enabled cost at <2%
of a CPU LeNet step). Everything here is jax-free at import: the async
socket tier records spans from processes that must never pay the jax
import.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

__all__ = ["SpanRecorder", "recorder", "span", "enabled", "NULL_SPAN"]


class _NullSpan:
    """Shared do-nothing context manager — the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = NULL_SPAN = _NullSpan()


def _annotation(name: str, args):
    """``jax.profiler.TraceAnnotation(name, **args)``, entered — or None in
    a process that has not imported jax (nothing here ever imports it)."""
    cls = getattr(getattr(sys.modules.get("jax"), "profiler", None),
                  "TraceAnnotation", None)
    if cls is None:
        return None
    ann = cls(name, **args) if args else cls(name)
    ann.__enter__()
    return ann


class _Span:
    __slots__ = ("_rec", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str, cat: str, args):
        self._rec = rec
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self._ann = _annotation(self.name, self.args)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._rec._record(self.name, self.cat, self._t0, t1 - self._t0,
                          self.args)
        return False


class SpanRecorder:
    """Bounded, thread-safe buffer of completed spans.

    ``maxlen`` bounds memory on long runs (oldest spans fall off — the
    timeline is a sliding window, like LatencyWindow); ``dump()`` writes
    the Chrome trace-event JSON atomically (tmp + rename) so a reader
    polling the file mid-run never sees a torn document.
    """

    def __init__(self, maxlen: int = 65536):
        self.enabled = False
        self._events: deque = deque(maxlen=maxlen)
        # re-entrant: a collector run can start between any two bytecodes,
        # this module's own included, and its gc_pause span is recorded on
        # the thread it interrupted
        self._lock = threading.RLock()
        self._gc_span: Optional[_Span] = None
        self._t0 = time.perf_counter()
        self._epoch_us = time.time() * 1e6 - self._t0 * 1e6
        self.dropped = 0          # spans recorded past maxlen (overwrote)

    # ---- lifecycle ---------------------------------------------------- #
    def enable(self) -> None:
        self.enabled = True
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def disable(self) -> None:
        self.enabled = False
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self._gc_span = None

    def _on_gc(self, phase: str, info: Dict) -> None:
        """``gc.callbacks`` hook: one ``gc_pause`` span per collector run,
        on the thread the run interrupted. Runs never nest, so one slot
        holds the open span."""
        if phase == "start":
            self._gc_span = _Span(self, "gc_pause", "runtime",
                                  {"generation": info["generation"]})
            self._gc_span.__enter__()
        elif self._gc_span is not None:
            span, self._gc_span = self._gc_span, None
            span.args["collected"] = info["collected"]
            span.__exit__(None, None, None)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    # ---- recording ---------------------------------------------------- #
    def span(self, name: str, cat: str = "engine",
             args: Optional[Dict] = None):
        """Context manager timing one region. Near-free when disabled."""
        if not self.enabled:
            return _NULL
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "engine",
                args: Optional[Dict] = None) -> None:
        """Zero-duration marker (Chrome trace 'i' events)."""
        if not self.enabled:
            return
        ann = _annotation(name, args)
        if ann is not None:
            ann.__exit__(None, None, None)
        self._record(name, cat, time.perf_counter(), None, args)

    def complete(self, name: str, dur_s: float, cat: str = "engine",
                 args: Optional[Dict] = None) -> None:
        """A span that ended now and lasted ``dur_s``, for regions only
        their end reports (jax's compile-duration event)."""
        if not self.enabled:
            return
        self._record(name, cat, time.perf_counter() - dur_s, dur_s, args)

    def _record(self, name, cat, t0, dur_s, args) -> None:
        ev = (name, cat, t0, dur_s, threading.get_ident(), args)
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)

    # ---- export ------------------------------------------------------- #
    def trace_events(self) -> List[Dict]:
        """Chrome trace-event dicts ('X' complete / 'i' instant), ts/dur
        in microseconds on the wall-clock epoch."""
        with self._lock:
            snap = list(self._events)
        pid = os.getpid()
        out: List[Dict] = []
        for name, cat, t0, dur_s, tid, args in snap:
            ev: Dict = {
                "name": name, "cat": cat, "pid": pid, "tid": tid,
                "ts": round(self._epoch_us + t0 * 1e6, 3),
            }
            if dur_s is None:
                ev["ph"] = "i"
                ev["s"] = "t"
            else:
                ev["ph"] = "X"
                ev["dur"] = round(dur_s * 1e6, 3)
            if args:
                ev["args"] = dict(args)
            out.append(ev)
        return out

    def dump(self, path: str) -> str:
        """Write the Chrome trace JSON atomically; returns the path.
        A killed writer leaves only sweepable ``.tmp.<pid>`` litter."""
        doc = {"traceEvents": self.trace_events(),
               "displayTimeUnit": "ms",
               "metadata": {"tool": "poseidon_tpu spans",
                            "dropped_spans": self.dropped}}
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


# The process-wide recorder: the engine enables it under --trace_out and
# every instrumented module records into it (one timeline per process).
recorder = SpanRecorder()


def span(name: str, cat: str = "engine", args: Optional[Dict] = None):
    """Module-level shorthand for ``recorder.span`` (the common call)."""
    return recorder.span(name, cat, args)


def enabled() -> bool:
    return recorder.enabled
