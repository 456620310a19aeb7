"""Kernels layer: device milliseconds per step, forward, recomputed forward
and backward, in what follows every pass of a looped LM — the vocabulary
projection, the per-token loss over it, the exit gate — and in the
exit-weighted loss over all passes (the scopes the configuration names
under ``scopes.exit_heads``)."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "exit_heads")
