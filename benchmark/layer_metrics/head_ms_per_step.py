"""Kernels layer: device milliseconds per step in the head (the configuration's
``head`` scopes: the vocabulary projection, tied or not, and the loss over it):
forward, backward and replay. Granite's has the division by
``logits_scaling`` before the tied projection; GLM-4.7-Flash's is BOTH passes
of one matrix and their losses (``lm_{head,nll,loss}`` and
``mtp_{head,shift,nll,loss}``); Xing4.0's ``lm_{head,nll,loss}`` is over
16,384 rows."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "head")
