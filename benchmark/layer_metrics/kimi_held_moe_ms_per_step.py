"""Kernels layer: device milliseconds per step in the MOE layers of a rank
that holds 8 of 256 experts — the gates' top-8 and histogram, the sort, the
gather, the three grouped matmuls over the rows routed HERE (on the ladder's
rung the step's own routing took), the combine, forward, backward and
replay."""

import kimi_trace


def reduce(run: dict):
    return kimi_trace.part_ms_per_step(run, "held_moe")
