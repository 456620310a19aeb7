"""From a profiler trace (``*.xplane.pb``) to the numbers the benchmark
reports: device busy time, idle gaps, collective time and its exposed part,
the operations that took most time.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else. What was
seen by hand in a v5e trace of this program (PR 22) is written down in
``FORMATS``; the arithmetic below works on plain ``(name, start_ns, dur_ns)``
tuples and is checked on a small recorded trace in tests/.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Op = Tuple[str, float, float]            # name, start_ns, dur_ns
Interval = Tuple[float, float]           # start_ns, end_ns

# Where device operations sit in a trace, by platform (seen by hand in a
# v5e trace of this program, PR 22).
#  tpu: one plane per chip, "/device:TPU:<n>". Its "XLA Ops" line holds one
#       event per executed HLO operation, named by the operation's whole HLO
#       text; "Async XLA Ops" holds one event per asynchronous operation from
#       its -start to its -done (copies, slices, collectives), which is where
#       a collective's real duration is. "XLA Modules" holds whole programs
#       and would double the busy time; "TC Overlay" is empty.
#  cpu: (the --cpu-tiny rehearsal only) XLA:CPU has no device plane; its
#       thunks are host events carrying an ``hlo_op`` stat, told apart by
#       their ``device_ordinal`` stat, and named by the instruction alone.
FORMATS = {
    "tpu": {"plane": r"^/device:TPU:(\d+)$", "ops": "XLA Ops",
            "async": "Async XLA Ops"},
    "cpu": {"plane": r"^/host:CPU$", "stat": "hlo_op",
            "device_stat": "device_ordinal"},
}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
PALLAS_CALL = "pallas-call"


@functools.lru_cache(maxsize=None)     # a step's operations repeat every step
def label(text: str) -> str:
    """``<opcode> <instruction> <result shape>`` from an operation's HLO
    text (``%fusion.3 = f32[8]{0:T(256)} fusion(...), kind=kLoop``), at most
    96 characters; a Mosaic custom call's opcode reads ``pallas-call``. A
    name that is not HLO text (XLA:CPU's) is returned as it is."""
    instr, eq, rest = text.partition(" = ")
    op = re.search(r" ([a-z][\w-]*)\(", rest) if eq else None
    if not op:
        return text[:96]
    opcode = op.group(1)
    if opcode == "custom-call" and \
            'custom_call_target="tpu_custom_call"' in rest:
        opcode = PALLAS_CALL
    shape = re.sub(r"\{[^}]*\}", "", rest[:op.start()])
    return f"{opcode} {instr.lstrip('%')} {shape}"[:96]


def is_collective(name: str) -> bool:
    """By opcode or instruction name: ``all-reduce.3`` (XLA:CPU),
    ``all-reduce all-reduce.456 f32[1000000]`` (what the v5e ran: 47
    synchronous all-reduces a step), ``all-reduce-start ...``."""
    head = " ".join(name.split(" ")[:2])
    return any(c in head for c in COLLECTIVES)


def is_pallas(name: str) -> bool:
    return name.startswith(PALLAS_CALL + " ")


def newest_xplane(trace_dir: str) -> str:
    runs = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    if not runs:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return runs[-1]


def load(path: str, platform: str, align_name: Optional[str] = None) -> dict:
    """``{"devices": {chip: [Op, ...]}, "async": {chip: [Op, ...]},
    "align_ns": ...}``: every chip's executed operations, its asynchronous
    operations from start to done, and the start of the host event called
    ``align_name`` (None when absent), all on the trace's one clock."""
    from jax.profiler import ProfileData
    fmt = FORMATS[platform]
    plane_re = re.compile(fmt["plane"])
    out = {"devices": {}, "async": {}, "align_ns": None}
    for plane in ProfileData.from_file(path).planes:
        is_host = plane.name.startswith("/host:")
        match = plane_re.match(plane.name)
        if not match and not (is_host and align_name):
            continue
        for line in plane.lines:
            kind = None
            if match:
                kind = {fmt.get("ops", line.name): "devices",
                        fmt.get("async"): "async"}.get(line.name)
            if kind is None and not is_host:
                continue
            for ev in line.events:
                if is_host and ev.name == align_name \
                        and out["align_ns"] is None:
                    out["align_ns"] = float(ev.start_ns)
                if kind is None:
                    continue
                chip = match.group(1) if match.groups() else "0"
                if "stat" in fmt:
                    stats = dict(ev.stats)
                    if fmt["stat"] not in stats:
                        continue
                    chip = str(stats.get(fmt["device_stat"], 0))
                out[kind].setdefault(chip, []).append(
                    (label(ev.name), float(ev.start_ns),
                     float(ev.duration_ns)))
    for by_chip in (out["devices"], out["async"]):
        for ops in by_chip.values():
            ops.sort(key=lambda op: (op[1], -op[2]))
    return out


def step_starts(ops: List[Op], n_steps: int) -> List[float]:
    """Where each of ``n_steps`` identical steps begins on one chip's
    operation line: the starts of the operation that ran exactly once a
    step and came first (every step runs the same programs in the same
    order, so its k-th start is the k-th step's). Operations inside loops
    or conditionals, whose count follows the data, are not candidates."""
    count: Dict[str, int] = {}
    for name, _, _ in ops:
        count[name] = count.get(name, 0) + 1
    mark = next((name for name, _, _ in ops if count[name] == n_steps), None)
    if mark is None:
        raise RuntimeError(f"no operation ran exactly once in each of the "
                           f"{n_steps} traced steps: they cannot be told "
                           f"apart")
    return [start for name, start, _ in ops if name == mark]


def since_step(trace: dict, n_steps: int, first: int) -> Tuple[dict, float]:
    """``load``'s result from step ``first`` (counted from 0) of the
    ``n_steps`` it holds, chip by chip (the steps a window runs and does not
    count go), and the earliest such start: where the counted window
    opens."""
    cuts = {chip: step_starts(ops, n_steps)[first]
            for chip, ops in trace["devices"].items()}
    opens = min(cuts.values())
    return {"devices": {chip: [op for op in ops if op[1] >= cuts[chip]]
                        for chip, ops in trace["devices"].items()},
            "async": {chip: [op for op in ops
                             if op[1] >= cuts.get(chip, opens)]
                      for chip, ops in trace["async"].items()}}, opens


def union(ops: Iterable[Op]) -> List[Interval]:
    """Merged intervals in which at least one operation runs."""
    merged: List[List[float]] = []
    for _, start, dur in sorted(ops, key=lambda op: op[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(a, b) for a, b in merged]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(intervals: List[Interval]) -> List[Interval]:
    """The idle stretches between merged busy intervals."""
    return [(a[1], b[0]) for a, b in zip(intervals, intervals[1:])
            if b[0] > a[1]]


def _parents(ops: List[Op]) -> List[Optional[int]]:
    """For each operation the index of the innermost operation on the line
    that wholly contains it (a ``while`` or ``call`` holds its body's
    events); operations that merely overlap are siblings."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    parent: List[Optional[int]] = [None] * len(ops)
    stack: List[int] = []
    for i in order:
        _, start, dur = ops[i]
        while stack and start + dur > ops[stack[-1]][1] + ops[stack[-1]][2]:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return parent


def self_times(ops: List[Op]) -> List[float]:
    """Each operation's duration less the operations nested directly
    inside it."""
    own = [op[2] for op in ops]
    for i, p in enumerate(_parents(ops)):
        if p is not None:
            own[p] -= ops[i][2]
    return own


def leaves(ops: List[Op]) -> List[Op]:
    """Operations that hold no other operation (what the chip executed,
    without the control-flow shells around them)."""
    shells = {p for p in _parents(ops) if p is not None}
    return [op for i, op in enumerate(ops) if i not in shells]


def top_ops(ops: List[Op], n: int = 10) -> List[list]:
    """``[[name, seconds], ...]``: self time summed by operation name."""
    by_name: Dict[str, float] = {}
    for (name, _, _), own in zip(ops, self_times(ops)):
        by_name[name] = by_name.get(name, 0.0) + own
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def collective_time(ops: List[Op], async_ops: Iterable[Op] = ()
                    ) -> Tuple[float, float]:
    """``(total_ns, exposed_ns)`` of one chip's collectives: the union of
    their intervals — synchronous ones on the op line, asynchronous ones
    from start to done — and the part of it in which no other operation
    runs on that chip (waiting in a ``-done`` is not running)."""
    coll = union([op for op in ops if is_collective(op[0])]
                 + [op for op in async_ops if is_collective(op[0])])
    other = union(op for op in leaves(ops) if not is_collective(op[0]))
    return total(coll), total(coll) - overlap(coll, other)


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    shared = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            shared += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return shared


def attribute_gaps(idle: List[Interval], spans: List[dict],
                   n: int = 5) -> List[list]:
    """``[[what the host was doing, seconds], ...]`` for the ``n`` longest
    idle gaps: the span covering the gap's midpoint (the innermost, when
    spans nest), or ``unattributed``. ``spans`` carry ``name``,
    ``start_ns`` and ``dur_ns`` on the trace's clock."""
    out = []
    for start, end in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
        mid = (start + end) / 2
        covering = [s for s in spans
                    if s["start_ns"] <= mid <= s["start_ns"] + s["dur_ns"]]
        name = (min(covering, key=lambda s: s["dur_ns"])["name"]
                if covering else "unattributed")
        out.append([name, (end - start) / 1e9])
    return out


# --------------------------------------------------------------------------- #
# one trace window -> what the result line and the per-layer readers use
# --------------------------------------------------------------------------- #

def traced_devices(run: dict) -> Optional[Dict[str, List[Op]]]:
    """The per-chip operations a per-layer reader reduces, or None when the
    run carries no trace (or one without device operations)."""
    return (run.get("trace") or {}).get("devices") or None


def window(devices: Dict[str, List[Op]]) -> Interval:
    """First operation's start to last operation's end, over all chips."""
    return (min(ops[0][1] for ops in devices.values()),
            max(op[1] + op[2] for ops in devices.values() for op in ops))


def busy_seconds(devices: Dict[str, List[Op]]) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    return sum(total(union(ops)) for ops in devices.values()) \
        / len(devices) / 1e9


def summarize(trace: dict) -> dict:
    """``busy_s`` / ``window_s`` for the result line's ``device``, and the
    ``breakdown``: the ten operations that took most time and the five
    longest idle gaps by what the host was doing, both on the first chip."""
    devices = trace["devices"]
    if not devices:
        raise RuntimeError("the trace holds no device operation: wrong "
                           "plane/line names in device_trace.FORMATS?")
    start, end = window(devices)
    first = devices[sorted(devices)[0]]
    return {"busy_s": busy_seconds(devices), "window_s": (end - start) / 1e9,
            "breakdown": {
                "device_ops": top_ops(first, 10),
                "idle_gaps": attribute_gaps(gaps(union(first)),
                                            trace["spans"], 5)}}
