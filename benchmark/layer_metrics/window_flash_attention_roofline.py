"""Kernels layer: the flash kernels' share of their roofline in the window
ATTENTION layers (named ``l<i>_attn_window``) — the least time for what
``run["lm"]["flash_per_step"]["window"]`` requires (the BAND, W (W + 1) / 2 +
(S - W) W key positions a sequence; FLOPs over the bf16 peak or bytes over the
HBM peak, whichever is larger) over the Pallas calls' time inside those scopes,
replays included, in percent."""

import lm_trace


def reduce(run: dict):
    need = lm_trace.section(run).get("flash_per_step") or {}
    return lm_trace.roofline(
        run, need.get("window"),
        lm_trace.attention_ms_per_step(run, "window", pallas=True))
