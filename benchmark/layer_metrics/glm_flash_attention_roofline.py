"""Kernels layer: the flash-attention Pallas kernels' share of their roofline at
GLM-4.7-Flash's geometry (20 heads of 256 / 256, token-major, causal, six
blocks) — the least time for what ``run["lm"]["flash_per_step"]`` requires
(``flops_glm.flash_attention_step``: the key at its own width, the shared
part once) over the time of the Pallas custom calls inside the ATTENTION
layers' scopes, replays included, in percent. ``flash_attention_roofline``
under a name of this cell's own."""

import lm_trace


def reduce(run: dict):
    return lm_trace.roofline(run, lm_trace.section(run).get("flash_per_step"),
                             lm_trace.attention_ms_per_step(run, pallas=True))
