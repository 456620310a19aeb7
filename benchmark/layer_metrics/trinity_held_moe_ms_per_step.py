"""Kernels layer: device milliseconds per step in the MOE layers of a rank
that holds 16 of 128 experts — the gates' top-8 and histogram, the sort, the
gather, the three grouped matmuls over the rows routed HERE, the combine,
forward, backward and replay. The router and the shared expert are layers of
their own."""

import trinity_trace


def reduce(run: dict):
    return trinity_trace.part_ms_per_step(run, "held_moe")
