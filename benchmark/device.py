"""What the run is on: refuse anything but the chips the cell asks for, name
the device as jax reports it, look its peaks up, read its memory peak."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def require(chips: int, cpu_rehearsal: bool) -> dict:
    """``{"platform", "kind", "count"}`` of the devices jax holds, or exit
    code 2 with no result when they are not ``chips`` TPU chips. Only the
    tests' ``--cpu-tiny`` rehearsal may run on the CPU, and it says so."""
    import jax
    devices = jax.devices()
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
