"""Kernels layer: the recurrence's share of its roofline — the least time
the chip could take for the gated delta rule's REQUIRED work
(``flops_kimi.kda_scan_step``: 3 passes of 3 H d_k d_v multiply-accumulates
a token over the bf16 peak, or the bytes of q, k, v, g, beta, o and their
gradients once over the HBM peak, whichever is larger) over the device time
of the ``l<i>_kda_scan`` scopes, replays included. The chunked form's extra
products are not required, so no implementation reads above 100."""

import kimi_trace


def reduce(run: dict):
    return kimi_trace.roofline(
        run, "kda_scan_per_step",
        kimi_trace.part_ms_per_step(run, "kda_scan"))
