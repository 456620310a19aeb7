"""Kernels layer: the global layer's flash kernels' share of their roofline —
the least time the chip could take for what they require over half the
square of 16,384 positions (``flops_smallthinker.flash_attention_step``)
over the device time of the Pallas custom calls inside the
``l<i>_attn_global`` layer, replays included."""

import smallthinker_trace


def reduce(run: dict):
    return smallthinker_trace.flash_roofline(run, "global")
