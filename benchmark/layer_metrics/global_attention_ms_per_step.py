"""Kernels layer: device milliseconds per step in the global ATTENTION layers
(TYPE ``ATTENTION``, named ``l<i>_attn_global``): forward, backward and
replay, kernels and the operations around them."""

import lm_trace


def reduce(run: dict):
    return lm_trace.attention_ms_per_step(run, "global")
