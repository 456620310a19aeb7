"""Graph layer: the largest share of its assignments any ONE MoE layer held
in any display of the window (``l<i>_held_share`` per display, not the mean
over layers that ``trinity_held_assignment_share`` is), in percent. 12.5 = an
even split over 16 of 128; 25 is the ladder's prefix rung, and a layer above
it runs the full rung at those steps."""

import trinity_trace


def reduce(run: dict):
    by_layer = trinity_trace.published(run, "held_share_by_layer") or {}
    shares = [s for per_display in by_layer.values() for s in per_display]
    return 100.0 * max(shares) if shares else None
