"""Graph layer: the share of a step's token-to-expert assignments that fell
on an expert this rank holds, from the step's own routing as the MOE layers
publish it per display (``*_held_share``; mean over the window's displays and
layers), in percent. 50 = an even split over 8 of 16; the rows of the
grouped matmuls, and so ``held_moe_ms_per_step``, follow it."""

import zaya_trace


def reduce(run: dict):
    share = zaya_trace.mean_of(run, "held_share")
    return None if share is None else 100.0 * share
