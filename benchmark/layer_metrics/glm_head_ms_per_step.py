"""Kernels layer: device milliseconds per step in BOTH passes of the head and
their losses (the configuration's ``head`` scopes: ``lm_{head,nll,loss}`` and
``mtp_{head,shift,nll,loss}``, one matrix with two users): forward, backward
and replay. ``head_ms_per_step`` under a name of this cell's own."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "head")
