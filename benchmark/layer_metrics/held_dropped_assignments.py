"""Graph layer: the largest ``l<i>_dropped`` (``<p>dropped``) any display of
the window showed. 0 by construction — every row of a held expert lies in its
group — and a check of ``correct`` (the runner's ``no_dropped_token``)."""

import lm_trace


def reduce(run: dict):
    dropped = lm_trace.section(run).get("dropped")
    return max(dropped) if dropped else None
