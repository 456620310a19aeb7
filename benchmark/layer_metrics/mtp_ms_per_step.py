"""Kernels layer: device milliseconds per step in the multi-token-prediction
module (the configuration's ``mtp`` scopes, every ``mtp_*``: the embedding's
second lookup, the two norms, W_eh, the module's sparse block, its pass of the
shared head, the targets' shift and its loss): forward, backward and
replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "mtp")
