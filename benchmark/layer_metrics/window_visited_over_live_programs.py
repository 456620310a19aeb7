"""Kernels layer: programs the window arm of the flash kernels VISITS over the
programs that hold a live (query, key) pair, a head, summed over the three
kernels, from the program's own ``kernel_routes`` note (``fwd 1024x1024
21/24, dq ..., dkv ...``). 1.0 = only live blocks; the causal grid over a
window of W would read S / (2 W)."""

import lm_trace


def reduce(run: dict):
    return lm_trace.window_visited_over_live(run)
