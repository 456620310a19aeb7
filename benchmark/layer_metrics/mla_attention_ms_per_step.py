"""Kernels layer: device milliseconds per step in the MLA layers' ATTENTION
scopes (``l<i>_mla_attn``: head split and merge, the shared key part's
repeat to 32 heads and its gradient's sum, the three flash kernels at 192 /
128), forward, backward and what remat replays."""

import kimi_trace


def reduce(run: dict):
    return kimi_trace.part_ms_per_step(run, "mla_attention")
