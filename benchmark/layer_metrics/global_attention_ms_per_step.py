"""Kernels layer: device milliseconds per step in the GLOBAL layers' ATTENTION
scopes (``l<i>_attn_global``: no positions; head split and merge, the
key-value heads' repeat and the three causal flash kernels), forward,
backward and what remat replays."""

import lm_trace
import trinity_trace


def reduce(run: dict):
    if not trinity_trace.is_ours(run):
        return None
    return lm_trace.self_ms_per_step(
        run, lambda _, scope, kind: kind == "ATTENTION"
        and scope.endswith("_attn_global"))
