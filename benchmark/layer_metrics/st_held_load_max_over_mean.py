"""Graph layer: assignments at the fullest HELD expert over the held experts'
mean, from the step's own routing as the MOE layers publish it per display
(``*_expert_load``; mean over the window's displays and layers). 1.0 = the
16 held experts share their rows evenly."""

import smallthinker_trace


def reduce(run: dict):
    return smallthinker_trace.mean_of(run, "expert_load")
