"""Train-loop layer: the most one stall of the measured window lost, in
milliseconds (``stalls.longest_ms``; 0 for a window without one)."""

import stall_ledger


def reduce(run: dict):
    sec = stall_ledger.section(run)
    if sec is None:
        return None
    return float(sec["longest_ms"])
