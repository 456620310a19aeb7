"""Graph layer: the largest share of its assignments any ONE MoE layer held
in any display of the window (``l<i>_held_share`` per display, not the mean
over layers that ``kimi_held_assignment_share`` is), in percent. 3.125 = an
even split over 8 of 256; a layer above a rung of the ladder runs the next
one at those steps."""

import kimi_trace


def reduce(run: dict):
    by_layer = kimi_trace.published(run, "held_share_by_layer") or {}
    shares = [s for per_display in by_layer.values() for s in per_display]
    return 100.0 * max(shares) if shares else None
