"""Kernels layer: device milliseconds per step in the attention gate — its
projection ``l<i>_g`` (D x 4096), the sigmoid ``l<i>_gate_sig`` and the
product with the merged heads ``l<i>_gate_mul`` (the configuration's
``attention_gate`` scopes) — forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "attention_gate")
