"""Kernels layer: device milliseconds per step in the GLOBAL layer's
ATTENTION scope (``l<i>_attn_global``: head split and merge, the key-value
heads' repeat, no positions, the three flash kernels over the causal
triangle of 16 x 16 tiles of 1024), forward, backward and what remat
replays."""

import smallthinker_trace


def reduce(run: dict):
    return smallthinker_trace.attention_ms_per_step(run, "global")
