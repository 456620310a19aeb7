"""Train-loop layer: milliseconds per step the window lost to steps that
were long on the chip with the host waiting
(``stalls.lost_ms_by_cause.device`` over ``steps``): a MoE cell's routing,
a collective."""

import stall_ledger


def reduce(run: dict):
    return stall_ledger.per_step(
        run, lambda sec: (sec.get("lost_ms_by_cause") or {}).get("device",
                                                                 0.0))
