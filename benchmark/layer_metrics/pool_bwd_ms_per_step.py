"""Kernels layer: device milliseconds per step in the backward pass of the
POOLING layers — the Pallas pool-backward kernels and whatever copies and
converts the compiler put around them under the same scope."""

import scope_trace


def reduce(run: dict):
    return scope_trace.ms_per_step(run, layer_types=("POOLING",),
                                   phases=("bwd",))
