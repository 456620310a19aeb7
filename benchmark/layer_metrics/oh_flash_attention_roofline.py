"""Kernels layer: the full layers' causal flash kernels' share of their
roofline at 15 heads of 128 with no positions — the least time the chip
could take for what they require over the triangle
(``flops_olmo_hybrid.flash_attention_step``: 3 x 256 multiply-accumulates a
live pair a head) over the device time of the Pallas custom calls inside
the ``l<i>_attn_sdpa`` layers, replays included."""

import olmo_hybrid_trace


def reduce(run: dict):
    return olmo_hybrid_trace.roofline(
        run, "flash_per_step", olmo_hybrid_trace.flash_ms_per_step(run))
