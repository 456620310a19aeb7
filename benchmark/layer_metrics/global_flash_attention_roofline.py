"""Kernels layer: the flash kernels' share of their roofline in the global
ATTENTION layers (named ``l<i>_attn_global``) — the least time for what
``run["lm"]["flash_per_step"]["global"]`` requires (half the square;
FLOPs over the bf16 peak or bytes over the HBM peak, whichever is larger)
over the Pallas calls' time inside those scopes, replays included, in
percent."""

import lm_trace


def reduce(run: dict):
    need = lm_trace.section(run).get("flash_per_step") or {}
    return lm_trace.roofline(
        run, need.get("global"),
        lm_trace.attention_ms_per_step(run, "global", pallas=True))
