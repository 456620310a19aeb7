"""Kernels layer: device milliseconds per step in the sigmoid MOE_ROUTER
layers — the (128, 2048) product in f32, the sigmoid, top-8 of score + bias,
the renormalised weights and the bias's balancing rule — forward, backward
and replay."""

import trinity_trace


def reduce(run: dict):
    return trinity_trace.part_ms_per_step(run, "router")
