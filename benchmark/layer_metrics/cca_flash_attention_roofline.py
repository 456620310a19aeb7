"""Kernels layer: the causal flash-attention kernels' share of their roofline
at 8 query / 2 key-value heads — the least time the chip could take for what
they require (``flops_zaya.flash_attention_step``: FLOPs over the bf16 peak
or bytes over the HBM peak, whichever is larger; remat's second forward is
not required) over the device time of the Pallas custom calls inside the
ATTENTION layers, replays included."""

import lm_trace
import zaya_trace


def reduce(run: dict):
    lm = run.get("lm") or {}
    if not zaya_trace.is_ours(run) or not lm.get("peaks"):
        return None
    ms = lm_trace.pallas_ms_per_step(run, "ATTENTION")
    if not ms:
        return None
    need = lm["flash_per_step"]
    least_s = max(need["flops"] / lm["peaks"]["bf16_flops_per_s"],
                  need["bytes"] / lm["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
