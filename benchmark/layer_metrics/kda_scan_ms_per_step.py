"""Kernels layer: device milliseconds per step in the recurrence's own
scopes (``l<i>_kda_scan``: the chunk-local parts, the scan over chunks, the
backward's walk), forward, the one replay and backward, whatever implements
the scan."""

import kimi_trace


def reduce(run: dict):
    return kimi_trace.part_ms_per_step(run, "kda_scan")
