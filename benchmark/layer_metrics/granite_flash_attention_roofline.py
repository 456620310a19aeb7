"""Kernels layer: the flash-attention Pallas kernels' share of their roofline in
Granite's attention layer (32 query / 8 key-value heads of 64, head-major) —
the least time for what ``run["lm"]["flash_per_step"]`` requires
(``flops_granite.flash_attention_step``) over the time of the Pallas custom
calls inside the ATTENTION layers' scopes, replays included, in percent.
``flash_attention_roofline`` under a name of this cell's own."""

import lm_trace


def reduce(run: dict):
    return lm_trace.roofline(run, lm_trace.section(run).get("flash_per_step"),
                             lm_trace.attention_ms_per_step(run, pallas=True))
