"""Kernels layer: device milliseconds per step, forward, recomputed forward
and backward, in the dense gated FFN of every application of every layer —
the gate, up and down projections and the SiLU gate between them (the
scopes the configuration names under ``scopes.ffn``). Its two norms are
not in it."""

import looplm_trace


def reduce(run: dict):
    return looplm_trace.pattern_ms_per_step(run, "ffn")
