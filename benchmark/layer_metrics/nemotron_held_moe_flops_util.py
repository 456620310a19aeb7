"""Kernels layer: the held UNGATED experts' share of the MXU's peak — the
required FLOPs of the assignments the traced steps really routed to a held
expert (two products an assignment,
``flops_nemotron.expert_flops_per_assignment``) over
``nemotron_held_moe_ms_per_step`` x the bf16 peak: the shared
``held_moe_flops_util`` reading under this cell's own name."""

from layer_metrics.held_moe_flops_util import reduce  # noqa: F401
