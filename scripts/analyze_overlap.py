"""DWBP overlap proof from an xplane trace: do collectives co-run with compute?

The reference's signature result is that per-layer gradient sync threads
overlap communication with the remaining backward pass
(/root/reference/src/caffe/solver.cpp:419-449). Our rebuild emits the psums
mid-backward via custom_vjp taps and relies on XLA's latency-hiding
scheduler to overlap them. THIS script proves the mechanism from a trace
(`alexnet.dp4.resident`'s `collective_exposed_ms_per_step` is the
end-to-end reading): for every
collective op on the device timeline, how much of its duration co-runs with
at least one compute op.

Usage: python scripts/analyze_overlap.py <trace_dir>
       (trace_dir = what POSEIDON_BENCH_TRACE / --profile wrote; the newest
        plugins/profile/*/ *.xplane.pb inside it is used)

Prints ONE JSON line:
  {"metric": "dwbp_overlap_fraction", "value": 0..1,
   "collective_ms": N, "overlapped_ms": N, "n_collectives": N, ...}
"""

from __future__ import annotations

import glob
import json
import os
import sys

# HLO instruction names keep the jax primitive's label (psum.N, all_gather.N)
# as well as XLA's own collective spellings
COLLECTIVE_MARKERS = ("all-reduce", "all-gather", "all_gather", "psum",
                      "reduce-scatter", "reduce_scatter",
                      "collective-permute", "collective_permute",
                      "all-to-all", "all_to_all", "ppermute")


def find_xplane(trace_dir: str) -> str:
    pats = [os.path.join(trace_dir, "**", "*.xplane.pb")]
    hits = []
    for p in pats:
        hits += glob.glob(p, recursive=True)
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(hits, key=os.path.getmtime)


def load_device_events(path: str):
    """-> {plane_name: [(name, start_ps, dur_ps)]} from device-side xplanes.

    Kept per plane: each device/core has its own timeline, and overlap must
    be computed within one core — a collective on core 0 is NOT hidden by
    compute running on core 1."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:  # proto location moved across TF versions
        from xprof.protobuf import xplane_pb2  # type: ignore
    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    def plane_events(plane):
        emeta = {k: v.name for k, v in plane.event_metadata.items()}
        out = []
        for line in plane.lines:
            for ev in line.events:
                name = emeta.get(ev.metadata_id, "")
                start = line.timestamp_ns * 1000 + ev.offset_ps
                out.append((name, start, ev.duration_ps))
        return out

    device, rest = [], []
    for plane in xs.planes:
        pname = plane.name.lower()
        is_device = ("tpu" in pname or "device" in pname) and \
            "host" not in pname
        (device if is_device else rest).append(plane)
    planes = {p.name: plane_events(p) for p in device}
    planes = {k: v for k, v in planes.items() if v}
    if not planes:  # CPU smoke traces have only host planes
        planes = {p.name: plane_events(p) for p in rest}
        planes = {k: v for k, v in planes.items() if v}
    return planes


def _plane_overlap(events):
    """(collective_ps, overlapped_ps, n_colls, n_comp) for ONE timeline."""
    # drop python-frame ("$...") and paired end-marker host events
    events = [(n, s, d) for n, s, d in events
              if n and not n.startswith(("$", "end:"))]
    colls = [(s, s + d, n) for n, s, d in events
             if any(m in n.lower() for m in COLLECTIVE_MARKERS) and d > 0]
    comp = sorted((s, s + d) for n, s, d in events
                  if d > 0 and
                  not any(m in n.lower() for m in COLLECTIVE_MARKERS))
    # merge compute intervals
    merged = []
    for s, e in comp:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])

    import bisect
    starts = [m[0] for m in merged]

    def covered(a: float, b: float) -> float:
        tot = 0.0
        i = bisect.bisect_right(starts, a) - 1
        i = max(i, 0)
        while i < len(merged) and merged[i][0] < b:
            s, e = merged[i]
            tot += max(0.0, min(e, b) - max(s, a))
            i += 1
        return tot

    total = sum(e - s for s, e, _ in colls)
    over = sum(covered(s, e) for s, e, _ in colls)
    return total, over, len(colls), len(comp)


def overlap_fraction(planes) -> dict:
    """Aggregate per-plane (per-core) overlap: a collective only counts as
    hidden when compute on ITS OWN timeline covers it. Accepts either a
    {plane: events} dict or a bare event list (treated as one plane)."""
    if not isinstance(planes, dict):
        planes = {"<events>": planes}
    total = over = 0.0
    n_colls = n_comp = 0
    per_plane = {}
    for name, events in planes.items():
        t, o, nc, np_ = _plane_overlap(events)
        total += t
        over += o
        n_colls += nc
        n_comp += np_
        if nc:
            per_plane[name] = round(o / t, 4)
    return {
        "metric": "dwbp_overlap_fraction",
        "value": round(over / total, 4) if total else None,
        "collective_ms": round(total / 1e9, 3),
        "overlapped_ms": round(over / 1e9, 3),
        "n_collectives": n_colls,
        "n_compute_events": n_comp,
        "per_plane": per_plane,
    }


def main() -> int:
    if len(sys.argv) < 2:
        print("usage: analyze_overlap.py <profiler trace dir>",
              file=sys.stderr)
        return 2
    trace_dir = sys.argv[1]
    try:
        path = find_xplane(trace_dir)
        events = load_device_events(path)
        out = overlap_fraction(events)
        out["xplane"] = path
    except Exception as e:  # noqa: BLE001
        out = {"metric": "dwbp_overlap_fraction", "value": None,
               "error": f"{type(e).__name__}: {e}"}
    print(json.dumps(out), flush=True)
    return 0 if out.get("value") is not None else 1


if __name__ == "__main__":
    main()
