"""Kernels layer: device milliseconds per step, forward and backward, in
CONVOLUTION and INNER_PRODUCT layers — the only operations whose FLOPs
``mfu_required`` counts (``flops.py``)."""

import scope_trace


def reduce(run: dict):
    return scope_trace.ms_per_step(
        run, layer_types=("CONVOLUTION", "INNER_PRODUCT"))
