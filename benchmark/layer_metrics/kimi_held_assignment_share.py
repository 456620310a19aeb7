"""Graph layer: the share of a step's token-to-expert assignments (8 a
token) that fell on an expert this rank holds, from the step's own routing
as the MOE layers publish it per display (``*_held_share``; mean over the
window's displays and layers), in percent. 3.125 = an even split over 8 of
256; the rows of the grouped matmuls follow it."""

import kimi_trace


def reduce(run: dict):
    share = kimi_trace.mean_of(run, "held_share")
    return None if share is None else 100.0 * share
