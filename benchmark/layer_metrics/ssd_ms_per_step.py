"""Kernels layer: device milliseconds per step in the Mamba-2 mixers (the
configuration's ``ssd`` scopes: every ``l<i>_ssd_*`` layer — the two
projections, the splits, the short convolution, the step and the decay, the
scan, the gate, the output norm): forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "ssd")
