"""Step builder / sync layer: device milliseconds per step in the optimizer
update and the parameter arena's pack / unpack / views / grads (scopes the
program's map types ``update`` and ``arena``; mean over chips)."""

import scope_trace


def reduce(run: dict):
    return scope_trace.ms_per_step(run, roles=("update", "arena"))
