"""Kernels layer: device milliseconds per step in the MOE_ROUTER layers — the
down-projection, the mix with the layer before's state, the three-layer GELU
MLP, softmax, the biased argmax and the bias's balancing rule, all in f32 —
forward, backward and replay."""

import zaya_trace


def reduce(run: dict):
    return zaya_trace.part_ms_per_step(run, "router")
