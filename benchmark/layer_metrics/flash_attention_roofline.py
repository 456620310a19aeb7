"""Kernels layer: the flash-attention Pallas kernels' share of their roofline —
the least time the chip could take for what they REQUIRE
(``run["lm"]["flash_per_step"]``, the configuration's
``flops_*.flash_attention_step``: FLOPs over the bf16 peak or bytes over the
HBM peak, whichever is larger) over the time of the Pallas custom calls
inside the ATTENTION layers' scopes, replays included, in percent. The
kernels skip blocks above the diagonal, so the required work is the causal
half; what remat replays counts as zero."""

import lm_trace


def reduce(run: dict):
    return lm_trace.roofline(run, lm_trace.section(run).get("flash_per_step"),
                             lm_trace.attention_ms_per_step(run, pallas=True))
