"""Kernels layer: device milliseconds per step in the untied head over an
eighth of the vocabulary, and its loss: ``lm_head`` (forward, its replay and
both backward products over (16384, 18992) logits), ``lm_nll`` and
``lm_loss``."""

import smallthinker_trace


def reduce(run: dict):
    return smallthinker_trace.part_ms_per_step(run, "head")
