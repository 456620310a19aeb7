"""Kernels layer: the held experts' share of the compute roofline — the
required FLOPs of the assignments the TRACED steps really routed to an
expert held here (the MOE layers' ``held_share`` of the display those steps
fill x ``assignments_per_step`` x ``flops_per_assignment``,
``flops_xing.expert_flops_per_assignment``; what remat replays counts as
zero) over ``xing_held_moe_ms_per_step`` of the same steps x the chip's bf16
peak."""

import lm_trace


def reduce(run: dict):
    share = lm_trace.mean_of(run, "traced_held_share")
    if share is None:
        return None
    lm = run["lm"]
    need = share * lm["assignments_per_step"] * lm["flops_per_assignment"]
    return lm_trace.flops_util(run, need,
                               lm_trace.part_ms_per_step(run, "held_moe"))
