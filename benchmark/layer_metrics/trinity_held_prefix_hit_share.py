"""Graph layer: of the WINDOW's MoE layer-steps whose held arm runs a ladder
(``models/moe.held_row_ladder``), the share that ran on the prefix rung, in
percent: the Engine's counters ``held_prefix_hits`` / ``held_layer_steps``
(one count a layer a step, from the step's own ``l<i>_held_share``),
differenced over the window by the runner. 100 = no layer's held assignments
passed the rung (32,768 of 131,072 rows = 25%) at any step; a miss runs the
full rung and costs about three times the prefix's time. None where the
program counts no ladder."""

import trinity_trace


def reduce(run: dict):
    counted = trinity_trace.published(run, "held_prefix")
    if not counted or not counted["held_layer_steps"]:
        return None
    return 100.0 * counted["held_prefix_hits"] / counted["held_layer_steps"]
