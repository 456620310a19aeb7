"""Graph layer: device milliseconds per step in forward operations that
activation remat runs a second time, during backward (the instructions the
program's map lists under ``recomputed``): here every layer's stream passes
among them."""

import lm_trace


def reduce(run: dict):
    return lm_trace.recomputed_ms_per_step(run)
