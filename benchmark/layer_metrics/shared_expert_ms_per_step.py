"""Kernels layer: device milliseconds per step in the shared expert every
token takes (``l<i>_shared_{gate,up,act,down}``: a SiLU-gated MLP of 1024)
and its sum with the routed part (``l<i>_moe_sum``), forward, backward and
replay."""

import trinity_trace


def reduce(run: dict):
    return trinity_trace.part_ms_per_step(run, "shared_expert")
