"""Kernels layer: the ATTENTION layers' device time OUTSIDE their Pallas calls —
head split and merge, rotary positions (window layers), the key-value heads'
repeat to 32 query heads and its gradient's sum, ``rowsum(dO * O)``."""

import trinity_trace


def reduce(run: dict):
    return trinity_trace.attention_glue_ms_per_step(run)
