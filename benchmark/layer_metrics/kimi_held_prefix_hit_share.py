"""Graph layer: of the WINDOW's MoE layer-steps, the share that ran on the
ladder's FIRST rung (``models/moe.held_row_ladder``), in percent: the
Engine's counters ``held_prefix_hits`` / ``held_layer_steps`` (one count a
layer a step, from the step's own ``l<i>_held_share``), differenced over the
window by the runner. None where the program counts no ladder."""

import kimi_trace


def reduce(run: dict):
    counted = kimi_trace.published(run, "held_prefix")
    if not counted or not counted["held_layer_steps"]:
        return None
    return 100.0 * counted["held_prefix_hits"] / counted["held_layer_steps"]
