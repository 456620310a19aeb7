"""Kernels layer: the ATTENTION layers' device time OUTSIDE their Pallas calls —
head split and merge, rotary positions (window layers), the 4 key-value
heads' repeat to 28 query heads and its gradient's sum, ``rowsum(dO * O)``."""

import smallthinker_trace


def reduce(run: dict):
    return smallthinker_trace.attention_glue_ms_per_step(run)
