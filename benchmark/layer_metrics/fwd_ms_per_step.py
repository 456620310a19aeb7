"""Graph layer: device milliseconds per step in the forward pass of the
net's layers (self time of the operations the program's map tags ``fwd``;
mean over chips)."""

import scope_trace


def reduce(run: dict):
    return scope_trace.ms_per_step(run, phases=("fwd",))
