"""Host-side span timeline: the telemetry spine's wall-clock half.

``jax.named_scope`` + the compiled step's scope map (runtime/attribution.py,
published by the Engine as stats section ``step_scopes``) attribute DEVICE
time; this module attributes HOST time — wherever a thread of the training
process can hold a step up. A span is a context manager around one such
region; the recorder buffers them in a bounded thread-safe deque and dumps
Chrome trace-event JSON (``chrome://tracing`` / Perfetto load it directly).

Span names, by thread (``cat`` in brackets; PERF.md section 3 names the
reader of each). A thread's name here is its ROLE, the ``thread`` key of
its events:

- ``train`` (the thread that calls ``Engine.train``, which says so with
  ``set_role``): ``prefetch_wait`` [input], ``dispatch`` with its children
  ``dispatch_rng`` and ``dispatch_execute``, ``dispatch_window`` [step],
  ``hard_sync`` [sync], ``snapshot`` [ckpt], ``telemetry_dump`` [artifact];
- ``reader`` (``BatchPipeline._worker``): ``producer_read``,
  ``producer_queue_full`` [input];
- ``prefetcher`` (``DevicePrefetcher._worker``; the train thread on the
  CPU backend's passthrough arm): ``producer_h2d`` (the ``device_put``
  calls of one batch), ``producer_h2d_land`` (from their return until the
  batch's arrays are ready on the device: a wait this thread makes only
  while the recorder is enabled, for every ``LAND_EVERY``-th batch, and
  under no span for the batch before it, so that the span covers one
  copy alone on the link),
  ``producer_queue_full``;
- ``drainer`` (``AsyncScalarFetcher``): the instant ``step_done`` [step]
  (``iter``; the steps of one scan-chunk dispatch complete together and
  carry ``dispatch`` = their first);
- ``heartbeat`` (``_Heartbeat``, alive from ``enable()``, or from the end
  of the start-up phase where that is later, to ``disable()``):
  ``host_freeze`` [runtime], from the wake this thread asked for to the
  wake it got when that is more than ``threshold_s`` late: something that
  should have run and did not. Args ``ms``, ``cpu_ms`` (the process's CPU
  time over the stretch: near zero = the process was not running; near
  the stretch's length or more = a thread held the interpreter),
  ``nivcsw`` / ``majflt`` (``getrusage``'s involuntary context switches
  and major faults) and, where the cgroup's ``cpu.stat`` is readable,
  ``throttled_us``;
- whichever thread they happen on: ``gc_pause`` (a collector run, through
  ``gc.callbacks`` while enabled) and ``compile`` (jax's backend-compile
  event, through the Engine's listener) [runtime];
- async tier (``async_sender``, ``async_accept``, ``async_monitor``,
  ``async_serve``): ``async_push`` / ``async_pull`` / ``async_gate`` /
  ``async_admit`` / ``async_flush`` [async]; further roles with no span of
  their own yet: ``ckpt_writer``, ``stream_io``, ``metrics_server``.

A thread made with ``threading.Thread(name=<role>)`` needs nothing else:
the recorder reads the name once per thread. An event of a thread nobody
named carries no ``thread``. ``dump()`` also writes Chrome's
``thread_name`` records, so Perfetto labels the rows.

Spans of one step share identifiers: ``batch`` joins ``producer_read`` ->
``producer_h2d`` -> ``producer_h2d_land`` -> ``prefetch_wait``, ``iter``
joins ``prefetch_wait`` -> ``dispatch`` -> ``step_done``.

**The window's summary: stats section ``stalls``.** While the recorder is
enabled, every ``Engine.train`` call ends by reducing the recorder's events
since ``clear()`` to a ledger of its late steps (``stall_ledger`` below
says how; the Engine adds ``summary_ms``, what making it took):
``pace_ms`` (the median interval between completions), ``steps``,
``window_ms`` (first completion to last), ``stalls`` (how many),
``lost_ms`` (what the chip lost to them), ``lost_ms_by_cause`` (``device``:
the step was long on the chip with the host waiting in ``dispatch_window``
/ ``hard_sync``; ``input``: ``prefetch_wait``; ``dispatch``; ``gc``;
``artifact``: ``telemetry_dump`` / ``snapshot``; ``compile``; ``freeze``: a
``host_freeze`` covers half the excess; ``unnamed``: the train thread
inside no span, or inside one of a name this list does not have), ``longest_ms``,
``freeze_ms`` (all ``host_freeze`` time, stall or not), ``events_dropped``
and ``worst``: by ``iter``, the 16 stalls that lost most, each with
``at_ms``, ``interval_ms``, ``lost_ms``, ``cause`` and what the ``train``,
``reader`` and ``prefetcher`` threads were inside at the interval's
midpoint. A slow run is read from ``stats.yaml`` alone: ``lost_ms`` over
``window_ms`` says whether the window lost anything, ``lost_ms_by_cause``
to what, ``worst`` when and with which batch and step in flight, and
``freeze_ms`` whether the process itself was stopped.

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
import resource
import sys
import threading
import time
from bisect import bisect_right
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["SpanRecorder", "recorder", "span", "enabled", "NULL_SPAN",
           "stall_ledger"]


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


# the names threading gives a thread nobody named: no role
_UNNAMED_THREADS = ("Thread-", "MainThread", "Dummy-")


def _open_cpu_stat() -> Optional[int]:
    """This process's cgroup-v2 ``cpu.stat`` (``throttled_usec``) as an open
    descriptor, or None where it is not readable: a cgroup-v1 host, as the
    TPU host of PERF.md's runs is, has no such file."""
    path = ""
    try:
        with open("/proc/self/cgroup") as f:
            for line in f:
                if line.startswith("0::"):
                    path = line.strip()[3:]
        return os.open(f"/sys/fs/cgroup{path}/cpu.stat", os.O_RDONLY)
    except OSError:
        return None


class _Heartbeat:
    """The thread that sees what no span can hold: it asks to sleep
    ``period_s`` and, when it wakes more than ``threshold_s`` later than
    that, records ``host_freeze`` [runtime] from the wake it expected to the
    wake it got. Nothing of this process ran it in between although it
    should have: every thread was stopped (``cpu_ms`` near zero: the
    process was not running; ``throttled_us``, ``nivcsw`` and ``majflt``
    say how the machine took it away), or one thread held the interpreter
    (``cpu_ms`` near the stretch's length times the busy threads). In the
    profiler's trace it is a mark on this thread at the wake it GOT, whose
    ``ms`` says how far back it reaches (an annotation cannot start in the
    past). Lives from ``enable()`` to ``disable()``, outside the start-up
    phase (``SpanRecorder._beat``).

    A wake takes the interpreter from whichever thread holds it, so a wake
    that came on time reads the two clocks and nothing else; the counters
    (``getrusage``, ``cpu.stat``) are read on a late wake and on every
    ``readings_every``-th: ``cpu_ms`` is the stretch's own, ``nivcsw`` /
    ``majflt`` / ``throttled_us`` count from the last reading of them, at
    most that many periods before the stretch began."""

    # every wake asks the interpreter of whichever thread holds it: at 2 ms
    # ``alexnet.lmdb``'s ``device_put`` calls took 15% longer than with no
    # heartbeat, at 4 ms 10% (PERF.md section 6, PR 51, the fix round)
    period_s = 0.004
    # over the interpreter's switch interval (5 ms): a thread that asks for
    # the GIL while another runs Python waits that long by design
    threshold_s = 0.008
    readings_every = 64

    def __init__(self, rec: "SpanRecorder"):
        self._rec = rec
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, name="heartbeat",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()

    def _loop(self) -> None:
        cpu_stat = _open_cpu_stat()
        try:
            before, wakes = _readings(cpu_stat), 0
            while not self._stop.is_set():
                due = time.perf_counter() + self.period_s
                time.sleep(self.period_s)   # one call; an Event's wait is 20
                woke, wakes = time.perf_counter(), wakes + 1
                if woke - due > self.threshold_s \
                        or wakes % self.readings_every == 0:
                    after = _readings(cpu_stat)
                else:       # on time: the two clocks and nothing else
                    after = (time.process_time(),) + before[1:]
                self.beat(due, woke, before, after)
                before = after
        finally:
            if cpu_stat is not None:
                os.close(cpu_stat)

    def beat(self, due: float, woke: float, before: Tuple,
             after: Tuple) -> None:
        """One wake: asked for ``due``, got ``woke``; ``before`` and
        ``after`` are the readings (``_readings``) at the last wake and at
        this one."""
        if woke - due <= self.threshold_s:
            return
        args = {"ms": round((woke - due) * 1e3, 3),
                "cpu_ms": round((after[0] - before[0]) * 1e3, 3),
                "nivcsw": after[1] - before[1],
                "majflt": after[2] - before[2]}
        if after[3] is not None and before[3] is not None:
            args["throttled_us"] = round(after[3] - before[3], 1)
        ann = _annotation("host_freeze", args)
        if ann is not None:
            ann.__exit__(None, None, None)
        self._rec._record("host_freeze", "runtime", due, woke - due, args)


def _readings(cpu_stat: Optional[int]) -> Tuple:
    """(CPU seconds of the process, involuntary context switches, major
    page faults, microseconds throttled or None), all so far; ``cpu_stat``
    is what ``_open_cpu_stat`` gave."""
    use = resource.getrusage(resource.RUSAGE_SELF)
    throttled = None
    if cpu_stat is not None:
        for line in os.pread(cpu_stat, 4096, 0).decode().splitlines():
            if line.startswith("throttled_usec"):
                throttled = float(line.split()[1])
    return time.process_time(), use.ru_nivcsw, use.ru_majflt, throttled


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
        self._roles = threading.local()     # .role: this thread's row
        self._heartbeat: Optional[_Heartbeat] = None
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
        self._beat()

    def _beat(self) -> None:
        """Start the heartbeat, if the recorder is enabled and the start-up
        phase has closed: that phase's spans tile their parents to a
        fraction of a millisecond and account for all of its time already,
        and every wake of this thread takes the interpreter from the one
        that is starting up. ``end_startup()`` calls this too."""
        with self._lock:
            if self.enabled and not self.startup_open \
                    and self._heartbeat is None:
                self._heartbeat = _Heartbeat(self)
                self._heartbeat.start()

    def disable(self) -> None:
        self.enabled = False
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self._gc_span = None
        with self._lock:
            beat, self._heartbeat = self._heartbeat, None
        if beat is not None:
            beat.stop()

    # ---- whose thread -------------------------------------------------- #
    def set_role(self, role: str) -> None:
        """Name the calling thread's row of the timeline (``Engine.train``
        says ``train``). A thread made with ``threading.Thread(name=...)``
        needs no call: its name is its role."""
        self._roles.role = role

    def _role(self) -> Optional[str]:
        """The calling thread's role, read once per thread: what
        ``set_role`` said, else the name the thread was made with; None for
        a thread nobody named (``Thread-7 (...)``, ``MainThread``)."""
        try:
            return self._roles.role
        except AttributeError:
            name = threading.current_thread().name
            role = None if name.startswith(_UNNAMED_THREADS) else name
            self._roles.role = role
            return role

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
        ev = (name, cat, t0, dur_s, threading.get_ident(), args,
              self._role())
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
            self._beat()
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
        for _, _, t0, dur_s, *_ in top:
            a, b = max(t0, reached), min(t0 + dur_s, end)
            if b > a:
                named += b - a
                reached = b
        doc["stretch_s"] = round(end - begin, 3)
        doc["coverage"] = round(named / (end - begin), 4) if end > begin \
            else 0.0
        doc["timeline"] = rows = {}
        for name, _, t0, dur_s, *_ in top:
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
        for name, _, _, dur_s, *_ in events:
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
        ev = (name, cat, t0, dur_s, threading.get_ident(), args,
              self._role())
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)

    # ---- the window's summary ------------------------------------------ #
    def stalls(self, max_in_flight: int = 1) -> Dict:
        """The stall ledger of the events since ``clear()`` (the stats
        section ``stalls``; ``stall_ledger`` says how it is made)."""
        with self._lock:
            events, dropped = list(self._events), self.dropped
        return stall_ledger(events, max_in_flight, dropped)

    # ---- export ------------------------------------------------------- #
    def trace_events(self, startup: bool = False) -> List[Dict]:
        """Chrome trace-event dicts ('X' complete / 'i' instant), ts/dur
        in microseconds on the wall-clock epoch: the window's, behind the
        start-up phase's with ``startup``. ``thread`` is the role of the
        event's thread, where it has one."""
        with self._lock:
            snap = (self._startup if startup else []) + list(self._events)
        pid = os.getpid()
        out: List[Dict] = []
        for name, cat, t0, dur_s, tid, args, role in snap:
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
            if role is not None:
                ev["thread"] = role
            out.append(ev)
        return out

    def dump(self, path: str) -> str:
        """Write the Chrome trace JSON atomically; returns the path.
        A killed writer leaves only sweepable ``.tmp.<pid>`` litter.
        Chrome's ``thread_name`` records label the rows by role."""
        events = self.trace_events(startup=True)
        rows = {(e["pid"], e["tid"]): e["thread"]
                for e in events if "thread" in e}
        doc = {"traceEvents": [
                   {"name": "thread_name", "cat": "__metadata", "ph": "M",
                    "pid": pid, "tid": tid, "args": {"name": role}}
                   for (pid, tid), role in rows.items()] + events,
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


# What a span on the train thread means for a late step (``stall_ledger``):
# the thread's time is filed under the innermost span open, a stretch inside
# none, or inside a span of another name, under ``unnamed``.
_STALL_CATEGORY = {
    "dispatch_window": "device", "hard_sync": "device",
    "prefetch_wait": "input", "producer_h2d": "input",
    "producer_h2d_land": "input", "producer_queue_full": "input",
    "dispatch": "dispatch", "dispatch_rng": "dispatch",
    "dispatch_execute": "dispatch",
    "gc_pause": "gc", "telemetry_dump": "artifact", "snapshot": "artifact",
    "compile": "compile"}
# a late step: its interval passes the pace by more than the larger of these
STALL_FLOOR_S = 0.002
STALL_SHARE = 0.10
WORST_STALLS = 16
_WHOSE = ("train", "reader", "prefetcher", "drainer")


def _self_time_segments(spans: Sequence[Tuple[float, float, str]]):
    """Disjoint (start, end, category) stretches of ONE thread's timeline
    from its (start, end, category) spans: the innermost span open names
    each stretch; where none is open there is no stretch."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []      # (end, category), innermost last
    cursor = float("-inf")
    ends = float("inf")                      # a last turn closes what is open
    for t0, t1, cat in sorted(spans, key=lambda s: (s[0], -s[1])) \
            + [(ends, ends, "")]:
        while stack and stack[-1][0] <= t0:
            end, closed = stack.pop()
            if end > cursor:
                segs.append((cursor, end, closed))
                cursor = end
        if t0 > cursor:
            if stack:
                segs.append((cursor, t0, stack[-1][1]))
            cursor = t0
        stack.append((t1, cat))
    return segs


def _time_by_category(segs, starts, a: float, b: float) -> Dict[str, float]:
    """Seconds of ``segs`` inside (a, b] by category; the rest of the
    stretch is ``unnamed``."""
    out: Dict[str, float] = {}
    named = 0.0
    i = max(0, bisect_right(starts, a) - 1)
    while i < len(segs) and segs[i][0] < b:
        t0, t1, cat = segs[i]
        over = min(t1, b) - max(t0, a)
        if over > 0:
            out[cat] = out.get(cat, 0.0) + over
            named += over
        i += 1
    out["unnamed"] = max(0.0, (b - a) - named)
    return out


def _usual_by_category(segs, bounds: Sequence[float]) -> Dict[str, float]:
    """The median over the intervals between ``bounds`` of the seconds of
    ``segs`` in each, by category (``unnamed``: the rest of the interval):
    what a step usually costs the thread. One pass over both."""
    n = len(bounds) - 1
    cols: Dict[str, List[float]] = {}
    k = 0
    for t0, t1, cat in segs:
        if t1 <= bounds[0]:
            continue
        if t0 >= bounds[n]:
            break
        while k < n - 1 and bounds[k + 1] <= t0:
            k += 1
        j = k
        while j < n and bounds[j] < t1:
            over = min(t1, bounds[j + 1]) - max(t0, bounds[j])
            if over > 0:
                col = cols.get(cat)
                if col is None:
                    col = cols[cat] = [0.0] * n
                col[j] += over
            j += 1
    named = [sum(vals) for vals in zip(*cols.values())] or [0.0] * n
    cols["unnamed"] = [max(0.0, bounds[i + 1] - bounds[i] - named[i])
                       for i in range(n)]
    return {cat: sorted(col)[n // 2] for cat, col in cols.items()}


def _open_at(spans, starts, t: float) -> Optional[str]:
    """The innermost of one thread's spans open at ``t``, as
    ``name batch=.. iter=..``; None where the thread is in none."""
    best = None
    i = bisect_right(starts, t)
    for t0, t1, name, args in reversed(spans[max(0, i - 8):i]):
        if t0 <= t < t1 and (best is None or t1 - t0 < best[0]):
            best = (t1 - t0, name, args)
    if best is None:
        return None
    ids = [f"{k}={best[2][k]}" for k in ("batch", "iter")
           if best[2] and k in best[2]]
    return " ".join([best[1]] + ids)


def stall_ledger(events: Sequence[Tuple], max_in_flight: int = 1,
                 dropped: int = 0) -> Dict:
    """Every late step of a window, with a cause from inside the program.

    ``events`` are the recorder's (name, category, start, seconds or None,
    thread id, args, role) tuples. The ``step_done`` instants give each
    step's completion, their intervals g, and the pace p = the median
    interval. An interval is LATE when g > p + max(``STALL_FLOOR_S``,
    ``STALL_SHARE`` p); what the next ``max_in_flight`` intervals run short
    of p comes off its excess g - p (a completion the drainer SAW late,
    followed by a burst, cost the chip nothing), and it is a stall when
    what stays, its ``lost_ms``, still passes that threshold. Its cause is
    read off the train thread over the look-back (previous completion less
    ``max_in_flight - 1`` paces, this completion]: a host delay starves
    the chip one queue later. The thread's time there by category
    (``_STALL_CATEGORY``), each less its median per interval times the
    steps looked back over: the category that GREW most. ``freeze``
    overrides it where the ``host_freeze`` time there, less ITS median per
    interval times those steps (a 316 MB ``device_put`` holds the
    interpreter 9 ms in every step of ``alexnet.lmdb``), covers half the
    excess.

    Keys: ``pace_ms``, ``steps``, ``window_ms`` (first completion to last),
    ``stalls``, ``lost_ms``, ``lost_ms_by_cause``, ``longest_ms`` (the
    most one stall lost), ``freeze_ms`` (all ``host_freeze`` time, stall
    or not), ``events_dropped`` and ``worst``: by ``iter``, the at most
    ``WORST_STALLS`` stalls that lost most, each with ``at_ms`` (from the
    first completion), ``interval_ms``, ``lost_ms``, ``cause`` and the
    innermost span open at the interval's midpoint on the ``train``,
    ``reader``, ``prefetcher`` and ``drainer`` threads."""
    depth = max(1, int(max_in_flight))
    done: Dict = {}               # one completion a dispatch: (time, iter)
    steps = 0
    freezes: List[Tuple[float, float]] = []
    for ev in events:
        if ev[0] == "step_done":
            steps += 1
            done[ev[5].get("dispatch", ev[5]["iter"])] = (ev[2], ev[5]["iter"])
        elif ev[0] == "host_freeze":
            freezes.append((ev[2], ev[2] + ev[3]))
    at = sorted(done.values())
    doc: Dict = {"pace_ms": 0.0, "steps": steps, "window_ms": 0.0,
                 "stalls": 0, "lost_ms": 0.0, "lost_ms_by_cause": {},
                 "longest_ms": 0.0,
                 "freeze_ms": round(sum(b - a for a, b in freezes) * 1e3, 3),
                 "events_dropped": dropped, "worst": {}}
    if len(at) < 3:
        return doc
    gaps = [b[0] - a[0] for a, b in zip(at, at[1:])]
    pace = sorted(gaps)[len(gaps) // 2]
    late = max(STALL_FLOOR_S, STALL_SHARE * pace)
    doc["pace_ms"] = round(pace * 1e3, 3)
    doc["window_ms"] = round((at[-1][0] - at[0][0]) * 1e3, 3)

    short = [max(0.0, pace - g) for g in gaps]
    found = []                    # (lost, excess, index of the interval)
    for i, g in enumerate(gaps):
        if g <= pace + late:
            continue
        lost = g - pace
        for j in range(i + 1, min(i + 1 + depth, len(gaps))):
            took = min(short[j], lost)
            short[j] -= took
            lost -= took
        if lost > late:
            found.append((lost, g - pace, i))
    if not found:
        return doc

    by_role: Dict[str, List] = {role: [] for role in _WHOSE}
    for name, _, t0, dur_s, _, args, role in events:
        if role in by_role and dur_s is not None:
            by_role[role].append((t0, t0 + dur_s, name, args))
    for spans in by_role.values():
        spans.sort(key=lambda s: s[0])
    segs = _self_time_segments(
        [(t0, t1, _STALL_CATEGORY.get(name, "unnamed"))
         for t0, t1, name, _ in by_role["train"]])
    seg_starts = [s[0] for s in segs]
    bounds = [t for t, _ in at]
    usual = _usual_by_category(segs, bounds)
    # the heartbeat is one thread: its freezes lie one behind the other
    usual_frozen = _usual_by_category(
        [(f0, f1, "freeze") for f0, f1 in sorted(freezes)],
        bounds).get("freeze", 0.0)
    begin = min(e[2] for e in events)
    role_starts = {role: [s[0] for s in spans]
                   for role, spans in by_role.items()}

    rows = []
    by_cause: Dict[str, float] = {}
    for lost, excess, i in found:
        a, b = at[i][0], at[i + 1][0]
        back = max(begin, a - (depth - 1) * pace)
        looked = 1.0 + (a - back) / pace
        grew = {cat: t - usual.get(cat, 0.0) * looked for cat, t in
                _time_by_category(segs, seg_starts, back, b).items()}
        cause = max(grew, key=grew.get) if by_role["train"] else "unnamed"
        frozen = sum(max(0.0, min(f1, b) - max(f0, back))
                     for f0, f1 in freezes) - usual_frozen * looked
        if frozen >= 0.5 * excess:
            cause = "freeze"
        row = {"at_ms": round((b - at[0][0]) * 1e3, 3),
               "interval_ms": round((b - a) * 1e3, 3),
               "lost_ms": round(lost * 1e3, 3), "cause": cause}
        for role in _WHOSE:
            held = _open_at(by_role[role], role_starts[role], (a + b) / 2)
            if held is not None:
                row[role] = held
        rows.append((lost, at[i + 1][1], row))
        by_cause[cause] = by_cause.get(cause, 0.0) + lost * 1e3
    doc["stalls"] = len(rows)
    doc["lost_ms"] = round(sum(r[0] for r in rows) * 1e3, 3)
    doc["longest_ms"] = round(max(r[0] for r in rows) * 1e3, 3)
    doc["lost_ms_by_cause"] = {c: round(ms, 3) for c, ms in by_cause.items()}
    rows.sort(key=lambda r: -r[0])
    doc["worst"] = {it: row for _, it, row in rows[:WORST_STALLS]}
    return doc


# The process-wide recorder: the engine enables it under --trace_out and
# every instrumented module records into it (one timeline per process).
recorder = SpanRecorder()


def span(name: str, cat: str = "engine", args: Optional[Dict] = None):
    """Module-level shorthand for ``recorder.span`` (the common call)."""
    return recorder.span(name, cat, args)


def enabled() -> bool:
    return recorder.enabled
