"""Graph layer: the share of all expert assignments that went to an expert
this chip holds, mean over the window's displays and MoE layers
(``l<i>_held_share``, the step's own routing), in percent. An even split
reads held / all experts; the required FLOPs assume it, so read
``mfu_required`` beside this."""

import lm_trace


def reduce(run: dict):
    share = lm_trace.mean_of(run, "held_share")
    return None if share is None else 100.0 * share
