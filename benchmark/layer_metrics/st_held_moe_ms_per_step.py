"""Kernels layer: device milliseconds per step in the MOE layers of a rank
that holds 16 of 64 ReGLU experts — the gates' top-6 and histogram, the sort,
and the chunk loop's trips (gather, three grouped matmuls at F 768 over the
rows routed HERE, the gate-zero count, the scatter-add), forward, backward
and replay. The router is a layer of its own."""

import smallthinker_trace


def reduce(run: dict):
    return smallthinker_trace.part_ms_per_step(run, "held_moe")
