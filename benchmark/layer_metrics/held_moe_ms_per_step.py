"""Kernels layer: device milliseconds per step in the MOE layers of a rank
that holds part of the experts — the choice's top-k and histogram, the sort,
the gather, the three grouped matmuls over the rows routed HERE, the combine,
forward, backward and replay. The router is a layer of its own
(``router_ms_per_step``)."""

import zaya_trace


def reduce(run: dict):
    return zaya_trace.part_ms_per_step(run, "held_moe")
