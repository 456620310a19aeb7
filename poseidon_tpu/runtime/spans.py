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

**The start-up phase.** From the package's first import until the first
train step's result is ready, the recorder keeps the spans of category
``startup`` whether or not it is enabled: every second before the first
step has a name. They are made with ``recorder.startup(name)`` (never in
the hot path: a start-up span always times its region, so that the
``engine_build`` timer and ``compiled_step.phases`` / ``.seconds`` of every
Engine are read off it, and is KEPT only while the phase is open). Each
carries its parent's name (``args.parent``: the start-up span open on its
thread when it began) and the identifiers that are there (``iter`` 0, the
AOT key's first 12 characters). By parent, in order:

- ``cli_setup`` (``cmd_train`` before the Engine exists: distributed
  init, compile-cache switch-on, policy) > ``backend_init`` (the first
  touch of the backend; ~0 when a caller touched it first);
- ``engine_build`` (``Engine.__init__``) > ``pipeline_open`` (per phase:
  source open, native reader, reader thread), ``net_build`` (prototxt
  load, ``Net`` for train and test, sharding and remat plan),
  ``step_build`` (train / scan / eval step builders), ``param_init`` (the
  fillers and the solver state, to the point the leaves are ready);
- ``restore`` (``restore_from``, when one happens); ``initial_test`` (the
  solver's ``test_initialization`` sweep); ``first_batch_wait`` (the train
  thread's wait for batch 0);
- ``step_load`` (``Engine._resolve_aot_step``, args ``key`` and ``route`` =
  ``loaded`` | ``compiled`` | ``xla_cache``) > ``step_key`` (imports, the
  sources' fingerprint, the key), ``aot_read``, ``aot_unpack``,
  ``aot_deserialize`` (a load) | ``step_trace_lower``, ``step_compile``,
  ``aot_store`` > ``aot_serialize``, ``aot_pack``, ``aot_write`` (a
  compile), then ``step_text`` (the executable's text and the passes over
  it) and ``scope_map``;
- ``first_step`` (the first dispatch of the step until its result is
  ready, program load onto the chip included);
- ``compile`` under whichever of these is open: every backend compile jax
  reports (or fetch from the XLA cache), with the ``program``'s name. It
  keeps its reporter's category (``runtime``): one that jax reports on a
  thread with no start-up span open (a harness's own compile) is counted
  in ``compiles`` / ``compile_s`` and is no row of ``timeline`` and no
  named time in ``coverage``.

When the first step is done the Engine calls ``end_startup()``: the phase
closes for good, the recorder is as it was before this phase existed
(disabled unless ``--trace_out`` or a harness enabled it), and the summary
it returns becomes stats section ``startup`` (``stats.yaml``,
``stats.snapshot()``): ``route`` and the other facts noted at the
boundaries, ``timeline`` (the top-level spans in order, each ``at_s`` from
the process's start as the OS gives it and ``dur_s``, so that what a
CALLER did shows as the gaps), ``spans`` (seconds by name), ``compiles`` /
``compile_s`` outside ``step_load``, ``xla_cache_hits``, ``coverage`` (time
under top-level spans over ``stretch_s``, ``cli_setup``'s start ->
``first_step``'s end) and ``events_dropped`` (the phase holds at most ``startup_cap`` events).
A slow restart is read from that section alone: ``route`` says whether the
step was loaded, ``spans`` which part of the load, the build or the first
step took the time, and a gap in ``timeline`` that it was not this program.
The events themselves survive ``clear()`` and are written by ``dump()``
ahead of the window, on the same clock as ``dispatch`` / ``hard_sync``.

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
STARTUP = "startup"      # the category of the start-up phase's spans


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


class _StartupSpan(_Span):
    """A span of the start-up timeline (``SpanRecorder.startup``): always
    times its region and knows its parent, the start-up span open on this
    thread when it began. ``dur_s`` and ``children`` (seconds by name of the
    spans that closed directly under it) are there once it has closed."""

    __slots__ = ("parent", "children", "dur_s", "_open")

    def __init__(self, rec: "SpanRecorder", name: str, args):
        super().__init__(rec, name, STARTUP, dict(args or ()))
        self.children: Dict[str, float] = {}

    def __enter__(self):
        self._open = self._rec._open_startup_spans()
        # a namesake still open is what an exception left behind (a span
        # opened by hand, as `engine_build` is): no span nests in its own
        # name, so it goes, with whatever was open above it
        for i, span in enumerate(self._open):
            if span.name == self.name:
                del self._open[i:]
                break
        self.parent = self._open[-1] if self._open else None
        if self.parent is not None:
            self.args["parent"] = self.parent.name
        self._open.append(self)
        return super().__enter__()

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.dur_s = t1 - self._t0
        # itself and whatever an exception left open above it (nothing,
        # when a later namesake has already taken this one off)
        if self in self._open:
            del self._open[self._open.index(self):]
        if self.parent is not None:
            kids = self.parent.children
            kids[self.name] = kids.get(self.name, 0.0) + self.dur_s
        self._rec._record_startup(self.name, STARTUP, self._t0, self.dur_s,
                                  self.args)
        return False


def _process_start(now: float) -> float:
    """The ``perf_counter`` reading at which the OS started this process
    (Linux: ``/proc/self/stat``'s start time against the boot clock, to a
    clock tick); ``now`` where the OS does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return now
    return now - age if 0.0 <= age < 86400.0 * 365 else now


class SpanRecorder:
    """Bounded, thread-safe buffer of completed spans.

    ``maxlen`` bounds memory on long runs (oldest spans fall off — the
    timeline is a sliding window, like LatencyWindow); ``dump()`` writes
    the Chrome trace-event JSON atomically (tmp + rename) so a reader
    polling the file mid-run never sees a torn document.
    """

    startup_cap = 512         # events the start-up phase holds

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
        # the start-up phase (module docstring): its events live beside the
        # window, where clear() does not reach them
        self._t_process = _process_start(self._t0)
        self._begin_startup()

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
        """Empty the window. The start-up phase's events stay."""
        with self._lock:
            self._events.clear()
            self.dropped = 0

    # ---- the start-up phase ------------------------------------------- #
    def _begin_startup(self) -> None:
        """Open the start-up phase: a new recorder does, once (and a test
        that runs a second `train` in its process)."""
        with self._lock:
            self.startup_open = True
            self.startup_dropped = 0
            self._startup: List[tuple] = []
            self._startup_facts: Dict = {}
            self._startup_stacks = threading.local()

    def _open_startup_spans(self) -> List[_StartupSpan]:
        try:
            return self._startup_stacks.open
        except AttributeError:
            self._startup_stacks.open = []
            return self._startup_stacks.open

    def startup(self, name: str, args: Optional[Dict] = None) -> _StartupSpan:
        """A span of the start-up timeline. It times its region whatever
        the recorder's state; the event is kept while the phase is open
        (after it, like any other span: while the recorder is enabled)."""
        return _StartupSpan(self, name, args)

    def note(self, add: bool = False, **facts) -> None:
        """Facts known at a start-up boundary (the step's route, the bytes
        of a serialized executable): they go into the phase's summary.
        With ``add`` they count up (XLA cache hits)."""
        if self.startup_open:
            with self._lock:
                for name, value in facts.items():
                    if add:
                        value += self._startup_facts.get(name, 0)
                    self._startup_facts[name] = value

    def _record_startup(self, name, cat, t0, dur_s, args) -> None:
        if not self.startup_open:
            if self.enabled:
                self._record(name, cat, t0, dur_s, args)
            return
        ev = (name, cat, t0, dur_s, threading.get_ident(), args)
        with self._lock:
            if len(self._startup) >= self.startup_cap:
                self.startup_dropped += 1
            else:
                self._startup.append(ev)

    def end_startup(self) -> Dict:
        """Close the phase and summarise it (the stats section
        ``startup``; the module docstring says what each key is)."""
        with self._lock:
            self.startup_open = False
            events = sorted(self._startup, key=lambda e: e[2])
            doc = {"xla_cache_hits": 0, **self._startup_facts,
                   "events_dropped": self.startup_dropped}
        # the timeline's rows and what counts as named time: the program's
        # own spans, not a compile jax reported on a thread with none open
        top = [e for e in events
               if e[1] == STARTUP and "parent" not in (e[5] or ())]
        loads = [(e[2], e[2] + e[3]) for e in events if e[0] == "step_load"]
        other = [e[3] for e in events if e[0] == "compile"
                 and not any(a <= e[2] and e[2] + e[3] <= b for a, b in loads)]
        doc["compiles"] = len(other)
        doc["compile_s"] = round(sum(other), 3)
        # the program's own stretch, and how much of it has a name: the
        # union of the top-level spans (a compile on another thread may
        # overlap one)
        begin = next((e[2] for e in top if e[0] == "cli_setup"),
                     top[0][2] if top else 0.0)
        end = max((e[2] + e[3] for e in top if e[0] == "first_step"),
                  default=max((e[2] + e[3] for e in top), default=begin))
        named, reached = 0.0, begin
        for _, _, t0, dur_s, _, _ in top:
            a, b = max(t0, reached), min(t0 + dur_s, end)
            if b > a:
                named += b - a
                reached = b
        doc["stretch_s"] = round(end - begin, 3)
        doc["coverage"] = round(named / (end - begin), 4) if end > begin \
            else 0.0
        doc["timeline"] = rows = {}
        for name, _, t0, dur_s, _, _ in top:
            row = rows.setdefault(
                name, {"at_s": round(t0 - self._t_process, 3), "dur_s": 0.0,
                       "n": 0})
            row["dur_s"] += dur_s
            row["n"] += 1
        for row in rows.values():
            row["dur_s"] = round(row["dur_s"], 3)
            if row["n"] == 1:           # said only of a name seen again
                del row["n"]
        spans: Dict[str, float] = {}
        for name, _, _, dur_s, _, _ in events:
            spans[name] = spans.get(name, 0.0) + dur_s
        doc["spans"] = {name: round(s, 3) for name, s in spans.items()}
        return doc

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
        their end reports (jax's compile-duration event). While the
        start-up phase is open it is one of its events, under the start-up
        span open on this thread."""
        if self.startup_open:
            args = dict(args or ())
            open_ = self._open_startup_spans()
            if open_:
                args["parent"] = open_[-1].name
            self._record_startup(name, cat, time.perf_counter() - dur_s,
                                 dur_s, args)
        elif self.enabled:
            self._record(name, cat, time.perf_counter() - dur_s, dur_s, args)

    def _record(self, name, cat, t0, dur_s, args) -> None:
        ev = (name, cat, t0, dur_s, threading.get_ident(), args)
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)

    # ---- export ------------------------------------------------------- #
    def trace_events(self, startup: bool = False) -> List[Dict]:
        """Chrome trace-event dicts ('X' complete / 'i' instant), ts/dur
        in microseconds on the wall-clock epoch: the window's, behind the
        start-up phase's with ``startup``."""
        with self._lock:
            snap = (self._startup if startup else []) + list(self._events)
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
        doc = {"traceEvents": self.trace_events(startup=True),
               "displayTimeUnit": "ms",
               "metadata": {"tool": "poseidon_tpu spans",
                            "dropped_spans": self.dropped,
                            "dropped_startup_spans": self.startup_dropped}}
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
