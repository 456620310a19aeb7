"""Kernels layer: the dense FFN's share of the compute roofline — the
required FLOPs of its three projections (``flops_olmo_hybrid``: 3 x D x I a
token and layer, three passes; remat's second forward counts as zero) over
``oh_ffn_ms_per_step`` x the chip's bf16 peak."""

import olmo_hybrid_trace


def reduce(run: dict):
    return olmo_hybrid_trace.flops_util(
        run, "ffn", olmo_hybrid_trace.part_ms_per_step(run, "ffn"))
