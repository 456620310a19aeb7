"""Kernels layer: the gated FFN's share of the compute roofline — the
required FLOPs of its three projections (``flops_looplm``: 3 x D x F a token
and application, three passes; remat's second forward counts as zero) over
``ffn_ms_per_step`` x the chip's bf16 peak."""

import looplm_trace


def reduce(run: dict):
    if not run.get("peak_flops_per_s"):
        return None
    ms = looplm_trace.pattern_ms_per_step(run, "ffn")
    if not ms:
        return None
    return 100.0 * run["lm"]["flops_per_step"]["ffn"] \
        / (ms / 1e3 * run["peak_flops_per_s"])
