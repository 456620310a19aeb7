"""Kernels layer: device milliseconds per step, forward and backward, in the
MOE layers — router, top-k, the sort by expert, the three grouped matmuls,
the weighted combine and both auxiliary losses."""

import scope_trace


def reduce(run: dict):
    if not run.get("lm"):
        return None
    return scope_trace.ms_per_step(run, layer_types=("MOE",))
