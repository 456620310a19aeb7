"""Graph layer: assignments to a HELD expert that no expert computed, the
largest per-display value the MOE layers published in the window
(``*_dropped``). Dropless by construction: 0, a check of ``correct``; an
assignment to an absent expert is not a drop, it is another rank's."""

import smallthinker_trace


def reduce(run: dict):
    dropped = smallthinker_trace.published(run, "dropped")
    return max(dropped) if dropped else None
