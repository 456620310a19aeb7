"""Kernels layer: device milliseconds per step in the WINDOW layers' ATTENTION
scopes (``l<i>_attn_window``: head split and merge, rotary positions, the
key-value heads' repeat and the three flash kernels over the band), forward,
backward and what remat replays."""

import lm_trace
import trinity_trace


def reduce(run: dict):
    if not trinity_trace.is_ours(run):
        return None
    return lm_trace.self_ms_per_step(
        run, lambda _, scope, kind: kind == "ATTENTION"
        and scope.endswith("_attn_window"))
