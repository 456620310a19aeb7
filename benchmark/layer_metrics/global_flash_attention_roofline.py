"""Kernels layer: the global layers' causal flash kernels' share of their
roofline at 32 query / 4 key-value heads — the least time the chip could
take for what they require over the triangle
(``flops_trinity.flash_attention_step``) over the device time of the Pallas
custom calls inside the ``l<i>_attn_global`` layers, replays included."""

import trinity_trace


def reduce(run: dict):
    return trinity_trace.flash_roofline(run, "global")
