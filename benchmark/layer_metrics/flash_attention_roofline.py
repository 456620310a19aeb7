"""Kernels layer: the flash-attention Pallas kernels' share of their roofline —
the least time the chip could take for what they REQUIRE
(``run["lm"]["flash_per_step"]``, the configuration's
``flops_*.flash_attention_step``: FLOPs over the bf16 peak or bytes over the
HBM peak, whichever is larger) over the time of the Pallas custom calls
inside the ATTENTION layers' scopes, replays included, in percent. The
kernels skip blocks above the diagonal, so the required work is the causal
half; what remat replays counts as zero. The geometries: Granite 32 query /
8 key-value heads of 64, head-major; GLM-4.7-Flash 20 heads of 256 / 256,
token-major, six blocks; Xing4.0 32 heads of 192 / 128, head-major, five
blocks (the two latent forms: the key at its own width, the shared part
once)."""

import lm_trace


def reduce(run: dict):
    return lm_trace.roofline(run, lm_trace.section(run).get("flash_per_step"),
                             lm_trace.attention_ms_per_step(run, pallas=True))
