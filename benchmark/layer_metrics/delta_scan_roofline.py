"""Kernels layer: the delta-rule scan's share of its roofline — the least time
for what ``run["lm"]["delta_scan_per_step"]`` requires (the configuration's
``flops_*.kda_scan_step`` / ``gdn_scan_step``: 3 passes of 3 H d_k d_v MACs a
token over the bf16 peak, or q, k, v, g, beta, o and their gradients once
over the HBM peak, whichever is larger; padded lanes, the replay and the
chunked form's extra products count zero) over ``delta_scan_ms_per_step``,
in percent."""

import lm_trace


def reduce(run: dict):
    return lm_trace.roofline(
        run, lm_trace.section(run).get("delta_scan_per_step"),
        lm_trace.part_ms_per_step(run, "delta_scan"))
