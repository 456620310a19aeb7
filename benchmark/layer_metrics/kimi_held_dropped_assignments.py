"""Graph layer: assignments to a HELD expert that no expert computed, the
largest per-display value the MOE layers published in the window
(``*_dropped``). Dropless by construction: 0, a check of ``correct``."""

import kimi_trace


def reduce(run: dict):
    dropped = kimi_trace.published(run, "dropped")
    return max(dropped) if dropped else None
