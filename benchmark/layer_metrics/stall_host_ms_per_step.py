"""Train-loop layer: milliseconds per step the window lost to stalls whose
cause lies on the host (``stalls.lost_ms_by_cause`` less ``device``, over
``steps``): input, dispatch, collector, artifacts, a compile, a freeze of
the whole process, or the train thread inside no span."""

import stall_ledger


def reduce(run: dict):
    return stall_ledger.per_step(run, stall_ledger.host_lost_ms)
