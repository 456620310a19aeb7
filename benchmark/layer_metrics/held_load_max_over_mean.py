"""Graph layer: the fullest held expert's rows over the held experts' mean,
mean over the window's displays and MoE layers (``l<i>_expert_load``,
``<p>expert_load``: the step's own routing). 1.0 = an even load over the
experts held here."""

import lm_trace


def reduce(run: dict):
    return lm_trace.mean_of(run, "expert_load")
