"""Kernels layer: the recurrence's share of its roofline — the least time
the chip could take for Gated DeltaNet's REQUIRED work at the published
96 / 192 (``flops_olmo_hybrid.gdn_scan_step``: 3 passes of 3 H d_k d_v
multiply-accumulates a token over the bf16 peak, or the bytes of q, k, v, g,
beta, o and their gradients once over the HBM peak, whichever is larger)
over the device time of the ``l<i>_gdn_scan`` scopes, replays included. The
chunked form's extra products and any padded lanes are not required, so no
implementation reads above 100."""

import olmo_hybrid_trace


def reduce(run: dict):
    return olmo_hybrid_trace.roofline(
        run, "gdn_scan_per_step",
        olmo_hybrid_trace.part_ms_per_step(run, "gdn_scan"))
