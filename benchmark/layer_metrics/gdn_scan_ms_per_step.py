"""Kernels layer: device milliseconds per step in the ``l<i>_gdn_scan``
scopes: the gated delta rule's recurrence with one decay a head, whichever
arm ``kda_route`` chose (forward, its one replay under remat, backward)."""

import olmo_hybrid_trace


def reduce(run: dict):
    return olmo_hybrid_trace.part_ms_per_step(run, "gdn_scan")
