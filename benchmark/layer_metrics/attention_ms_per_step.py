"""Kernels layer: device milliseconds per step, forward and backward, in the
ATTENTION layers — head split, rotary positions, the flash kernels (or the
dense arm), head merge. The q, k, v, o projections and the QK-norms are
layers of their own and not in it."""

import scope_trace


def reduce(run: dict):
    if not run.get("lm"):
        return None
    return scope_trace.ms_per_step(run, layer_types=("ATTENTION",))
