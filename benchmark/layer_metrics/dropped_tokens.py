"""Graph layer: assignments no expert computed, the largest per-display mean
the MOE layers published in the window. The layer is dropless: 0, and a
check of ``correct``."""


def reduce(run: dict):
    dropped = (run.get("lm") or {}).get("dropped")
    return max(dropped) if dropped else None
