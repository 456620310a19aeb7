"""Kernels layer: the flash-attention Pallas kernels' share of their roofline
at Xing4.0's geometry (32 heads of 192 / 128, head-major, causal, five
blocks) — the least time for what ``run["lm"]["flash_per_step"]`` requires
(``flops_xing.flash_attention_step``: the key at its own width, the shared
part once) over the time of the Pallas custom calls inside the ATTENTION
layers' scopes, replays included, in percent."""

import lm_trace


def reduce(run: dict):
    return lm_trace.roofline(run, lm_trace.section(run).get("flash_per_step"),
                             lm_trace.attention_ms_per_step(run, pallas=True))
