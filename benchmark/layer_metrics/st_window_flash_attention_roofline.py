"""Kernels layer: the window layers' flash kernels' share of their roofline —
the least time the chip could take for what they require over the BAND
(``flops_smallthinker.flash_attention_step``: W (W + 1) / 2 + (S - W) W key
positions a sequence at W 4096, S 16,384; FLOPs over the bf16 peak or bytes
over the HBM peak, whichever is larger; remat's second forward is not
required) over the device time of the Pallas custom calls inside the
``l<i>_attn_window`` layers, replays included."""

import smallthinker_trace


def reduce(run: dict):
    return smallthinker_trace.flash_roofline(run, "window")
