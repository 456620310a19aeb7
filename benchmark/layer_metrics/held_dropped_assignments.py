"""Graph layer: assignments to a HELD expert that no expert computed, the
largest per-display value the MOE layers published in the window
(``*_dropped``). The layer is dropless by construction (every row of a held
expert lies in its group of the grouped matmul): 0, a check of ``correct``;
an assignment to an absent expert is not a drop, it is the other rank's."""

import zaya_trace


def reduce(run: dict):
    dropped = (run.get("lm") or {}).get("dropped") \
        if zaya_trace.is_ours(run) else None
    return max(dropped) if dropped else None
