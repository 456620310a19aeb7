"""Train-loop layer: stalls per thousand steps of the measured window
(``stalls.stalls`` / ``steps`` x 1000)."""

import stall_ledger


def reduce(run: dict):
    per_step = stall_ledger.per_step(run, lambda sec: sec["stalls"])
    return None if per_step is None else 1000.0 * per_step
