"""Kernels layer: the MLA layers' causal flash kernels' share of their
roofline at 192-wide scores over 128-wide values — the least time the chip
could take for what they require over the triangle
(``flops_kimi.flash_attention_step``: 3 x (192 + 128) multiply-accumulates a
live pair a head) over the device time of the Pallas custom calls inside
the ``l<i>_mla_attn`` layers, replays included."""

import kimi_trace


def reduce(run: dict):
    return kimi_trace.roofline(run, "flash_per_step",
                               kimi_trace.mla_flash_ms_per_step(run))
