"""Graph layer: device milliseconds per step in the backward pass of the
net's layers (operations the program's map tags ``bwd``; mean over chips)."""

import scope_trace


def reduce(run: dict):
    return scope_trace.ms_per_step(run, phases=("bwd",))
