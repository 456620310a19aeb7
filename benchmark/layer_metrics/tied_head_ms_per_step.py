"""Kernels layer: device milliseconds per step in the head that shares the
embedding's table, and its loss: ``lm_head`` (forward, its replay, and both
backward products, the weight gradient landing on the tied leaf),
``lm_nll`` and ``lm_loss``."""

import zaya_trace


def reduce(run: dict):
    return zaya_trace.part_ms_per_step(run, "tied_head")
