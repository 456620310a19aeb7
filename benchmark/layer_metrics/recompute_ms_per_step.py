"""Graph layer: device milliseconds per step in forward operations that
activation remat runs a second time, during backward (the instructions the
program's map lists under ``recomputed``; they are part of ``bwd_ms_per_step``,
where they run). What the memory that remat frees costs in time. In Xing4.0
every layer's stream passes are among them."""

import lm_trace


def reduce(run: dict):
    return lm_trace.recomputed_ms_per_step(run)
