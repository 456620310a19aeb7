"""Kernels layer: device milliseconds per step in what stands around Mamba-2's
scan and projections (the configuration's ``ssd_glue`` scopes: the two
splits, the short convolution with its bias, the step and the decay, the gate,
the output norm): forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "ssd_glue")
