"""Kernels layer: the grouped matmuls' share of the compute roofline, routing
included — the required FLOPs of the experts a token is routed to
(``flops_lm``: k x 3 x D x F, three passes) over ``moe_ms_per_step`` x the
chip's bf16 peak."""

import scope_trace


def reduce(run: dict):
    if not run.get("lm") or not run["peak_flops_per_s"]:
        return None
    ms = scope_trace.ms_per_step(run, layer_types=("MOE",))
    if not ms:
        return None
    return 100.0 * run["lm"]["flops_per_step"]["experts"] \
        / (ms / 1e3 * run["peak_flops_per_s"])
