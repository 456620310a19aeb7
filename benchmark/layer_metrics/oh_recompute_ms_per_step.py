"""Graph layer: device milliseconds per step in forward operations that the
cell's ``--remat`` (one checkpoint a layer, one around the head) runs a
second time during backward: the instructions the program's map lists under
``recomputed``, the recurrence's one replay and the flash forward among
them. Part of ``bwd_ms_per_step``, where they run."""

import looplm_trace
import olmo_hybrid_trace


def reduce(run: dict):
    if not olmo_hybrid_trace.is_ours(run):
        return None
    return looplm_trace.recomputed_ms_per_step(run)
