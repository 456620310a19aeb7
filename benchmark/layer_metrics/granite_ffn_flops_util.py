"""Kernels layer: Granite's MLP's share of the compute roofline — its required
FLOPs a step (``run["lm"]["flops_per_step"]["ffn"]``; what remat replays counts
as zero) over ``granite_ffn_ms_per_step`` x the chip's bf16 peak, in percent.
``ffn_flops_util`` under a name of this cell's own."""

import lm_trace


def reduce(run: dict):
    return lm_trace.flops_util(
        run, (lm_trace.section(run).get("flops_per_step") or {}).get("ffn"),
        lm_trace.part_ms_per_step(run, "ffn"))
