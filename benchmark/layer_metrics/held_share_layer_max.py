"""Graph layer: the largest share of its assignments any ONE MoE layer held
in any display of the window (``l<i>_held_share`` per display, not the mean
over layers that ``held_assignment_share`` is), in percent. The held rows
run in chunks, so the fullest layer sets the longest loop of the step."""

import lm_trace


def reduce(run: dict):
    by_layer = lm_trace.section(run).get("held_share_by_layer") or {}
    shares = [s for per_display in by_layer.values() for s in per_display]
    return 100.0 * max(shares) if shares else None
