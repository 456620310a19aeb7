"""Train-loop layer: share of the measured window the chip lost to late
steps, by the program's own stall ledger (``stalls.lost_ms`` /
``window_ms``: the excess of every late completion over the window's pace,
less what the next ``max_in_flight`` completions made up).
``stall_share``'s question, answered from the completions."""

import stall_ledger


def reduce(run: dict):
    sec = stall_ledger.section(run)
    if sec is None or not sec.get("window_ms"):
        return None
    return 100.0 * sec["lost_ms"] / sec["window_ms"]
