"""Kernels layer: Mamba-2's scan's share of its roofline — the least time for
what ``run["lm"]["ssd_scan_per_step"]`` requires (the configuration's
``flops_granite.ssd_scan_step``: 3 passes of 2 H P N MACs a token at the
published 64 x 64 x 128 over the bf16 peak, or x, y, B, C, dt, a and their
gradients once over the HBM peak, whichever is larger; chunk products, padded
heads, the replay and every recomputation count zero) over
``ssd_scan_ms_per_step`` (the SCOPES ``l<i>_ssd_scan``, whichever arm runs),
in percent."""

import lm_trace


def reduce(run: dict):
    return lm_trace.roofline(
        run, lm_trace.section(run).get("ssd_scan_per_step"),
        lm_trace.part_ms_per_step(run, "ssd_scan"))
