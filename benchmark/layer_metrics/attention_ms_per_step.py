"""Kernels layer: device milliseconds per step in attention — the scopes the
configuration names ``attention`` where it names them, else every layer of
TYPE ``ATTENTION`` (OLMoE, Ouro): forward, backward and replay. What the
configurations name: Kimi's ``l<i>_mla_attn``; Olmo-Hybrid's and Granite's
whole ``l<i>_attn_*`` block (the four projections and the flash kernels,
Granite's at heads of 64, head-major; Olmo-Hybrid's norms with it);
GLM-4.7-Flash's whole ``<p>mla_*`` block, all six (the five projections, the
two latents' norms and split, the rotation, the shared key part's hand-over
to the heads, the flash kernels at heads of 256 / 256, token-major);
Xing4.0's ``l<i>_mla_*``, all five (the same parts, the head split and
YaRN's angles, heads of 192 / 128, head-major)."""

import lm_trace
import scope_trace


def reduce(run: dict):
    if not run.get("lm"):
        return None
    named = lm_trace.part_ms_per_step(run, "attention")
    if named is not None:
        return named
    return scope_trace.ms_per_step(run, layer_types=("ATTENTION",))
