"""Train-loop layer: share of what the window lost on the host whose cause
is ``unnamed``, the train thread inside no span
(``stalls.lost_ms_by_cause.unnamed`` / host-side ``lost_ms``): the ledger's
own coverage, as ``startup_coverage`` is the timeline's. 0 where nothing
was lost on the host."""

import stall_ledger


def reduce(run: dict):
    sec = stall_ledger.section(run)
    if sec is None:
        return None
    host = stall_ledger.host_lost_ms(sec)
    if host <= 0.0:
        return 0.0
    return 100.0 * sec["lost_ms_by_cause"].get("unnamed", 0.0) / host
