"""Kernels layer: device milliseconds per step in attention — the scopes the
configuration names ``attention`` where it names them (Kimi's
``l<i>_mla_attn``; Olmo-Hybrid's whole ``l<i>_attn_*`` block, projections and
norms with it), else every layer of TYPE ``ATTENTION`` (OLMoE, Ouro): forward,
backward and replay."""

import lm_trace
import scope_trace


def reduce(run: dict):
    if not run.get("lm"):
        return None
    named = lm_trace.part_ms_per_step(run, "attention")
    if named is not None:
        return named
    return scope_trace.ms_per_step(run, layer_types=("ATTENTION",))
