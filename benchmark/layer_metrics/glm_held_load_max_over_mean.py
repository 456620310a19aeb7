"""Graph layer: the fullest held expert's rows over the held experts' mean,
mean over the window's displays and the five sparse blocks
(``<p>expert_load``, the step's own routing). 1.0 = an even load over the
experts held here. ``held_load_max_over_mean`` under a name of this cell's
own."""

import lm_trace


def reduce(run: dict):
    return lm_trace.mean_of(run, "expert_load")
