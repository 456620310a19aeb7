"""What the run is on: refuse anything but the chips the cell asks for, name
the device as jax reports it, look its peaks up, read its memory peak."""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Seconds the runtime took to hand the chips over: the ONE call
# ``jax.devices()`` below, the process's first touch of the backend. Nothing of
# the repository runs inside it, and identical processes on one machine read
# 5.6-10.3 s for it within ten minutes (PERF.md, section 6, PR 63): run.py
# takes it out of ``setup_s`` and reports it beside, as ``backend_start_s``.
BACKEND_START_S = 0.0


def not_set_up() -> dict:
    """Seconds that run.py takes out of an end-to-end metric, by the metric's
    name: the runtime's start of the chips is not the repository's set-up."""
    return {"setup_s": BACKEND_START_S}


def layer_facts() -> dict:
    """What run.py adds to the runner's ``layers`` for the per-layer readers
    (``layer_metrics/backend_start_s.py``)."""
    return {"backend_start_s": BACKEND_START_S}


def require(chips: int, cpu_rehearsal: bool) -> dict:
    """``{"platform", "kind", "count"}`` of the devices jax holds, or exit
    code 2 with no result when they are not ``chips`` TPU chips. Only the
    tests' ``--cpu-tiny`` rehearsal may run on the CPU, and it says so."""
    global BACKEND_START_S
    import jax
    t = time.perf_counter()
    devices = jax.devices()
    BACKEND_START_S = time.perf_counter() - t
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    wanted = "cpu" if cpu_rehearsal else "tpu"
    if info["platform"] != wanted or info["count"] != chips:
        print(f"[benchmark] REFUSING: this cell needs {chips} {wanted} "
              f"device(s); jax holds {info}. Nothing was measured.",
              file=sys.stderr)
        raise SystemExit(2)
    return info


def peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or not isinstance(table[kind], dict):
        raise KeyError(
            f"no peak rates on record for device kind {kind!r}: add it to "
            f"benchmark/peaks.json with its source; a utilization against a "
            f"guessed peak is not a measurement")
    return table[kind]


def memory_peak_bytes() -> int:
    """Peak bytes of device memory taken on the fullest chip; 0 where the
    backend keeps no memory statistics (the CPU). Two counters add up to it:
    ``peak_bytes_in_use`` is the allocator's buffers (arrays: parameters,
    batches, results) and ``peak_bytes_reserved`` the scratch the runtime
    reserves "at the bottom of memory" for a loaded program — the compiled
    step's ``temp_size_in_bytes``, three quarters of the total for these
    nets. The first alone reads 2.7 GB where the chip refuses a program that
    needs 13.7 GB more (seen on the v5e, PR 22)."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak
