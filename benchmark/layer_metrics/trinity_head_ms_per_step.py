"""Kernels layer: device milliseconds per step in the untied head over an
eighth of the vocabulary, and its loss: ``lm_head`` (forward, its replay and
both backward products), ``lm_nll`` and ``lm_loss``."""

import trinity_trace


def reduce(run: dict):
    return trinity_trace.part_ms_per_step(run, "head")
