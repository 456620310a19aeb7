"""Measured comm accounting: collectives extracted from the compiled step.

The static table (comm_stats.py) predicts what each layer's strategy should
move per step. This module closes the loop by reading what XLA *actually
emitted*: the optimized HLO of the compiled train step, with every
all-reduce / all-gather / reduce-scatter / collective-permute, its payload
shape, dtype (so a bf16 wire is visible), and replica groups (so the
ici/dcn tier split is visible). The analog of the reference's runtime stats
(bg oplog bytes serialized, server push bytes — stats.hpp) for a compiled
SPMD program, where the data plane is fixed at compile time.

Usage:
    compiled = ts.lowerable.lower(params, state, batch, rng).compile()
    colls = parse_collectives(compiled.as_text())
    summary = measured_comm_summary(colls)
    # -> totals comparable against comm_stats.comm_summary()
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

# e.g.:  %all-reduce.12 = f32[500,300]{1,0} all-reduce(...), replica_groups={{0,1},{2,3}}
# XLA's combiner may merge several small collectives into one tuple-shaped
# op: %ar = (f32[500,300]{1,0}, f32[500]{0}) all-reduce(...)
_OP_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"\b(\w+)\[([\d,]*)\](?:\{[\d,]*\})?")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
# collective-permute carries source_target_pairs instead of replica_groups
# (e.g. source_target_pairs={{0,1},{1,2},...}); without parsing it the op
# fell to group_size=1 and the summary FILTERED the whole ring out —
# caught by the round-5 long-context capture reporting 0 collectives for
# a program with 48 ring permutes.
_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[^=]*?\})\}")


@dataclass
class Collective:
    kind: str            # all-reduce | all-gather | ...
    dtype: str           # dtype of the (first) payload
    shape: tuple         # shape of the (first) payload
    payload_bytes: int   # logical FULL payload (see _payload in the parser)
    group_size: int      # participants per replica group (1 = trivial)
    n_groups: int

    def wire_bytes_per_device(self) -> float:
        """Bytes each participant moves, ring-algorithm convention (the same
        convention comm_stats.py bills). ``payload_bytes`` is normalized by
        the parser to the FULL logical payload per kind: the reduced tensor
        (all-reduce), the gathered result (all-gather), the full input
        (reduce-scatter / all-to-all), the sent shard (permute)."""
        n = self.group_size
        if n <= 1:
            return 0.0
        if self.kind == "all-reduce":
            return 2.0 * (n - 1) / n * self.payload_bytes
        if self.kind in ("all-gather", "reduce-scatter", "all-to-all"):
            return (n - 1) / n * self.payload_bytes
        return float(self.payload_bytes)  # collective-permute


def _payload(kind: str, is_start: bool, tuple_bytes: float, n: int) -> float:
    """Normalize a parsed LHS byte sum to the FULL logical payload.

    Sync ops' LHS is the result alone (possibly a combined tuple of
    results); async ``-start`` ops carry (operands..., results...) — the
    operand buffers must not be double-counted. reduce-scatter's sync LHS
    is the per-device SHARD, so the full input is shard x n."""
    if n <= 1:
        return tuple_bytes
    if kind == "all-reduce":
        # operand == result, so -start tuples hold each payload twice
        return tuple_bytes / 2 if is_start else tuple_bytes
    if kind == "all-gather":
        # start tuple = operand (1/n of result) + result
        return tuple_bytes * n / (n + 1) if is_start else tuple_bytes
    if kind == "reduce-scatter":
        # start tuple = full operand + shard result; sync LHS = shard only
        return tuple_bytes * n / (n + 1) if is_start else tuple_bytes * n
    # collective-permute-start: (in, out, [u32 contexts]); all-to-all-start:
    # (in, out). in == out, contexts are scalar-sized noise.
    return tuple_bytes / 2 if is_start else tuple_bytes


def parse_collectives(hlo_text: str) -> List[Collective]:
    """All collectives in an (optimized) HLO module text, with payloads.

    Start/done pairs are collapsed (only ``-start`` ops carry the payload;
    plain ops appear in unoptimized HLO). Scalar payloads (e.g. the psum of
    ones behind a mean) are kept — filter by payload_bytes if unwanted."""
    out: List[Collective] = []
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        m = _OP_RE.search(line)
        # a ``-done`` op never matches (its name is followed by "-done(");
        # an operand named ``%copy-done.3`` must not hide the line
        if m is None:
            continue
        kind = m.group(1)
        is_start = line[m.start():m.end()].rstrip("(").endswith("-start")
        # sum every dtype[dims] between "= " and the op keyword (a single
        # shape, or the elements of a combined/async tuple); _payload then
        # normalizes to the full logical payload per kind
        lhs = line[line.index(" = ") + 3:m.start()]
        payload = 0
        first: Optional[tuple] = None
        for dm in _SHAPE_RE.finditer(lhs):
            dtype, dims = dm.group(1), dm.group(2)
            if dtype not in _DTYPE_BYTES:
                continue
            shape = tuple(int(d) for d in dims.split(",")) if dims else ()
            payload += (int(np.prod(shape)) if shape else 1) * \
                _DTYPE_BYTES[dtype]
            if first is None:
                first = (dtype, shape)
        if first is None:
            continue
        g = _GROUPS_RE.search(line)
        gi = _IOTA_GROUPS_RE.search(line)
        gp = _PAIRS_RE.search(line)
        if g:
            groups = [grp for grp in g.group(1).split("},{")]
            group_size = len(groups[0].strip("{}").split(","))
            n_groups = len(groups)
        elif gi:  # iota form: replica_groups=[n_groups,group_size]<=[N]
            n_groups, group_size = int(gi.group(1)), int(gi.group(2))
        elif gp:  # permute ring: participants = distinct devices in pairs
            devs = {d for pair in gp.group(1).split("},{")
                    for d in pair.strip("{}").split(",")}
            group_size, n_groups = max(len(devs), 2), 1
        else:
            group_size, n_groups = 1, 1
        out.append(Collective(kind=kind, dtype=first[0], shape=first[1],
                              payload_bytes=int(_payload(
                                  kind, is_start, payload, group_size)),
                              group_size=group_size,
                              n_groups=n_groups))
    return out


def gradient_all_reduce_census(hlo_text: str,
                               min_payload_bytes: int = 1024) -> tuple:
    """(gradient all-reduces, how many of them are asynchronous) in a
    compiled step: all-reduce ops with a non-trivial replica group and a
    payload big enough to be a gradient (the metrics / mean-divisor psums
    are scalars and fall under the threshold). Asynchronous means the
    compiler gave the collective a chance to run beside compute: an
    ``all-reduce-start`` / ``-done`` pair, or, on the TPU, an all-reduce
    inside an ``async_collective_fusion`` computation (its DMA phases
    interleave with the compute ops fused beside it). The TPU's text
    repeats such an all-reduce in the fusion's start and done computations
    (the ``async_collective_fusion_config`` in their backend_config tells
    them apart from a plain one); each is counted once, where it runs."""
    total = n_async = 0
    comp = ""
    for line in hlo_text.splitlines():
        if line.endswith("{") and line[:1] in ("%", "E"):
            comp = line.split(" ", 1)[0].lstrip("%")
            continue
        if "all-reduce" not in line:
            continue
        if not any(c.kind == "all-reduce" and c.group_size > 1
                   and c.payload_bytes >= min_payload_bytes
                   for c in parse_collectives(line)):
            continue
        if "all-reduce-start(" in line:
            n_async += 1
        elif '"async_collective_fusion_config"' in line:
            if not comp.startswith("async_collective_fusion"):
                continue        # the start / done clone of a fused one
            n_async += 1
        total += 1
    return total, n_async


def count_gradient_all_reduces(hlo_text: str,
                               min_payload_bytes: int = 1024) -> int:
    """Gradient all-reduces in a compiled step
    (``gradient_all_reduce_census``): at most one per synced leaf on the
    data-parallel path, fewer where the compiler's combiner merges."""
    return gradient_all_reduce_census(hlo_text, min_payload_bytes)[0]


# one stablehlo.all_reduce op, non-greedy to ITS result type: the reduction
# region between the op and its `-> tensor<...>` signature contains no `->`
_STABLEHLO_AR_RE = re.compile(
    r'"stablehlo\.all_reduce".*?\)\s*->\s*tensor<([0-9x]*)f32>', re.S)

# every collective kind the SPMD planner schedules, with its result type
# (all_reduce's region makes the result sit after the region's `->`; the
# others are plain one-line ops). bf16/f16 wires count too.
_STABLEHLO_COLL_RE = re.compile(
    r'"stablehlo\.(all_reduce|reduce_scatter|all_gather)"'
    r'.*?->\s*tensor<([0-9x]*)(f32|bf16|f16)>', re.S)


def collective_census_stablehlo(text: str,
                                min_elements: int = 256) -> Dict[str, int]:
    """Counts of all_reduce / reduce_scatter / all_gather ops in a LOWERED
    (pre-XLA) program whose payload is at least ``min_elements`` elements
    — the cheap, combiner-proof census the SPMD planner's
    ``collective_schedule`` is diffed against (analysis/contracts.py).
    Lowered counts are exact for the planned schedule: the flat buffer's
    chained buckets cannot legally merge, and XLA only ever merges,
    never splits."""
    out = {"all_reduce": 0, "reduce_scatter": 0, "all_gather": 0}
    for m in _STABLEHLO_COLL_RE.finditer(text):
        dims = m.group(2).rstrip("x")
        elems = int(np.prod([int(d) for d in dims.split("x")])) \
            if dims else 1
        if elems >= min_elements:
            out[m.group(1)] += 1
    return out


def count_gradient_all_reduces_stablehlo(text: str,
                                         min_elements: int = 256) -> int:
    """Gradient all-reduces in a LOWERED (pre-XLA) program — the cheap
    counter for tests that cannot afford a multi-minute CPU compile of a
    big net. Counts ``stablehlo.all_reduce`` ops whose f32 payload is big
    enough to be a gradient (metrics / mean-divisor psums are scalars).
    An upper bound on the compiled count: XLA's combiner may merge
    all-reduces but never splits one (``count_gradient_all_reduces``
    reads the compiled text where the compile is affordable)."""
    n = 0
    for m in _STABLEHLO_AR_RE.finditer(text):
        dims = m.group(1).rstrip("x")
        elems = int(np.prod([int(d) for d in dims.split("x")])) if dims else 1
        if elems >= min_elements:
            n += 1
    return n


def measured_comm_summary(colls: List[Collective],
                          min_payload_bytes: int = 16) -> Dict:
    """Totals comparable against comm_stats.comm_summary(): per-device wire
    bytes by collective kind and dtype, scalars filtered out."""
    total = 0.0
    by_kind: Dict[str, float] = {}
    by_dtype: Dict[str, float] = {}
    n_colls = 0
    for c in colls:
        if c.payload_bytes < min_payload_bytes or c.group_size <= 1:
            continue
        w = c.wire_bytes_per_device()
        total += w
        by_kind[c.kind] = by_kind.get(c.kind, 0.0) + w
        by_dtype[c.dtype] = by_dtype.get(c.dtype, 0.0) + w
        n_colls += 1
    return {
        "measured_bytes_per_step": int(total),
        "n_collectives": n_colls,
        "by_kind": {k: int(v) for k, v in sorted(by_kind.items())},
        "by_dtype": {k: int(v) for k, v in sorted(by_dtype.items())},
    }


def compare_static_vs_measured(static_summary: Dict,
                               measured: Dict) -> Dict:
    """The validation row for docs/performance-guide.md: static prediction
    vs compiled-program measurement and their ratio."""
    s = float(static_summary.get("total_bytes_per_step", 0))
    m = float(measured.get("measured_bytes_per_step", 0))
    return {
        "static_bytes_per_step": int(s),
        "measured_bytes_per_step": int(m),
        "measured_over_static": round(m / s, 4) if s else None,
    }
