"""Train-loop layer: milliseconds per step under ``host_freeze``, the
stretches in which the recorder's heartbeat thread was not run although it
had asked to be (``stalls.freeze_ms`` / ``steps``; every thread of the
process stopped, or one held the interpreter), stall or not."""

import stall_ledger


def reduce(run: dict):
    return stall_ledger.per_step(run, lambda sec: sec["freeze_ms"])
