"""Kernels layer: the causal flash-attention kernels' share of their roofline
at the looped LM's sequence length — the least time the chip could take for
what they require (``flops_looplm.flash_attention_step``: FLOPs over the bf16
peak or bytes over the HBM peak, whichever is larger; the forward that remat
replays is not required) over the device time of the Pallas custom calls
inside the ATTENTION layers, replays included."""

import lm_trace


def reduce(run: dict):
    lm = run.get("lm") or {}
    if "scopes" not in lm or not lm.get("peaks"):
        return None
    ms = lm_trace.pallas_ms_per_step(run, "ATTENTION")
    if not ms:
        return None
    need = lm["flash_per_step"]
    least_s = max(need["flops"] / lm["peaks"]["bf16_flops_per_s"],
                  need["bytes"] / lm["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
