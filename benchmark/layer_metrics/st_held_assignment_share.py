"""Graph layer: the share of a step's token-to-expert assignments (6 a token)
that fell on an expert this rank holds, from the step's own routing as the
MOE layers publish it per display (``*_held_share``; mean over the window's
displays and layers), in percent. 25 = an even split over 16 of 64; the
trips of the chunk loop follow it."""

import smallthinker_trace


def reduce(run: dict):
    share = smallthinker_trace.mean_of(run, "held_share")
    return None if share is None else 100.0 * share
