"""Kernels layer: device milliseconds per step in Granite's attention layer (the
configuration's ``attention`` scopes, the whole ``l<i>_attn_*`` block: the
four projections and the flash kernels at heads of 64, head-major): forward,
backward and replay. ``attention_ms_per_step`` under a name of this cell's own
(PERF.md section 7: the merge is a benchmark PR's)."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "attention")
