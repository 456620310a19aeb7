"""Kernels layer: the held experts' share of the compute roofline — the
required FLOPs of the assignments the TRACED steps really routed to an
expert held here (the MOE layers' ``held_share`` of the display those steps
fill x assignments x ``flops_smallthinker.expert_flops_per_assignment``;
what remat replays counts as zero) over ``st_held_moe_ms_per_step`` of the
same steps x the chip's bf16 peak."""

import smallthinker_trace


def reduce(run: dict):
    ms = smallthinker_trace.part_ms_per_step(run, "held_moe")
    share = smallthinker_trace.mean_of(run, "traced_held_share")
    if not ms or share is None or not run.get("peak_flops_per_s"):
        return None
    lm = run["lm"]
    need = share * lm["assignments_per_step"] * lm["flops_per_assignment"]
    return 100.0 * need / (ms / 1e3 * run["peak_flops_per_s"])
