"""Kernels layer: device milliseconds per step in Granite's SwiGLU MLP (the
configuration's ``ffn`` scopes: the input projection of 16,384, its split,
the gate and the output projection): forward, backward and replay.
``ffn_ms_per_step`` under a name of this cell's own."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "ffn")
