"""Kernels layer: device milliseconds per step in the three WINDOW layers'
ATTENTION scopes (``l<i>_attn_window``: head split and merge, rotary
positions, the 4 key-value heads' repeat to 28 query heads and the three
flash kernels over the band of W 4096 at S 16,384), forward, backward and
what remat replays."""

import smallthinker_trace


def reduce(run: dict):
    return smallthinker_trace.attention_ms_per_step(run, "window")
