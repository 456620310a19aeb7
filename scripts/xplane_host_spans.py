#!/usr/bin/env python3
"""What a profiler trace holds of the program's own timeline.

    python3 scripts/xplane_host_spans.py <trace_dir | file.xplane.pb> [gaps]

Reads the newest ``*.xplane.pb`` with ``jax.profiler.ProfileData`` and
prints, for every thread line of the host plane that carries a span of
``runtime/spans.py`` (entered there as a ``TraceAnnotation``), the span
names with their counts, and the ``train`` step annotations
(``StepTraceAnnotation`` in ``Engine.train``) with their ``step_num``s —
the check that host spans and device ops share one clock in one file.
With a number ``gaps``, also the that many longest idle gaps of the first
chip's "XLA Ops" line, each with every program span that overlaps it, by
thread line: what the train thread, the reader, the prefetcher and the
drainer were doing while the chip waited, read from the one file with no
join, and whether the heartbeat saw the whole process stopped there
(``host_freeze``: in the trace a mark at the wake the heartbeat got, drawn
here ``ms`` back from it). Exit code 1 when no such span is in the trace.
"""

from __future__ import annotations

import collections
import glob
import os
import sys
import warnings

SPANS = {"prefetch_wait", "dispatch", "dispatch_rng", "dispatch_execute",
         "dispatch_window", "hard_sync", "snapshot", "telemetry_dump",
         "producer_read", "producer_queue_full", "producer_h2d",
         "producer_h2d_land", "step_done", "gc_pause", "host_freeze",
         "resident_copy"}


def idle_gaps(ops, n):
    """The ``n`` longest stretches in which no operation of ``ops``
    ((start_ns, end_ns) pairs) runs."""
    gaps, busy_until = [], None
    for start, end in sorted(ops):
        if busy_until is not None and start > busy_until:
            gaps.append((busy_until, start))
        busy_until = end if busy_until is None else max(busy_until, end)
    return sorted(gaps, key=lambda g: g[0] - g[1])[:n]


def main(path: str, gaps: int = 0) -> int:
    from jax.profiler import ProfileData
    # ProfileData's stats iterator trips a DeprecationWarning per event
    warnings.filterwarnings("ignore", category=DeprecationWarning)
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            print(f"no *.xplane.pb under {path}", file=sys.stderr)
            return 1
        path = found[-1]
    print(f"trace: {path}")
    seen = 0
    chip_ops = None           # the first chip's executed operations
    host = {}                 # thread line -> its program spans
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            ops = sum(len(list(line.events)) for line in plane.lines)
            print(f"plane {plane.name}: {ops} events")
            if chip_ops is None and plane.name.startswith("/device:"):
                chip_ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                            for line in plane.lines
                            if line.name == "XLA Ops" for ev in line.events]
            continue
        for i, line in enumerate(plane.lines):
            names = collections.Counter()
            steps = []
            for ev in line.events:
                if ev.name in SPANS:
                    names[ev.name] += 1
                    args = dict(ev.stats)
                    start = ev.start_ns
                    if ev.name == "host_freeze" and "ms" in args:
                        # a mark at the wake the heartbeat GOT: the freeze
                        # reaches ``ms`` back from it (runtime/spans.py)
                        start -= int(float(args["ms"]) * 1e6)
                    host.setdefault(i, []).append(
                        (start, ev.start_ns + ev.duration_ns, ev.name, args))
                elif ev.name == "train":
                    steps.append(dict(ev.stats).get("step_num"))
            if names or steps:
                seen += sum(names.values())
                print(f"plane {plane.name} line {i} {line.name!r}: "
                      + ", ".join(f"{n} x{c}" for n, c in sorted(
                          names.items()))
                      + (f"; train steps {sorted(steps)}" if steps else ""))
    for a, b in idle_gaps(chip_ops or [], gaps):
        print(f"idle gap {(b - a) / 1e6:.3f} ms at {a / 1e6:.3f} ms:")
        for i, spans in sorted(host.items()):
            over = [f"{name}{args or ''} [{(s - a) / 1e6:+.3f} .. "
                    f"{(e - a) / 1e6:+.3f} ms]"
                    for s, e, name, args in spans if s < b and e > a]
            if over:
                print(f"  line {i}: " + "; ".join(over))
    return 0 if seen else 1


if __name__ == "__main__":
    if len(sys.argv) not in (2, 3):
        print(__doc__, file=sys.stderr)
        sys.exit(2)
    sys.exit(main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 0))
