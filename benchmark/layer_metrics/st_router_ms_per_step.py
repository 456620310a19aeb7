"""Kernels layer: device milliseconds per step in the softmax MOE_ROUTER
layers, which score the PRE-attention state — the (64, 2560) product in f32,
top-6 of the logits, the softmax over the chosen, the balance and z losses
over all 64 — forward, backward and replay."""

import smallthinker_trace


def reduce(run: dict):
    return smallthinker_trace.part_ms_per_step(run, "router")
