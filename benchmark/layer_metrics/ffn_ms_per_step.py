"""Kernels layer: device milliseconds per step in the dense FFN (the
configuration's ``ffn`` scopes: the gate and up projections, their product and
the down projection; Granite's SwiGLU MLP makes both in ONE input projection
of 16,384 and splits it): forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "ffn")
