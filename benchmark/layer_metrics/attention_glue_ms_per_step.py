"""Kernels layer: device milliseconds per step the ATTENTION layers spend
OUTSIDE their Pallas calls (head split and merge, rotary positions, the
key-value heads' repeat, ``rowsum(dO * O)``), plus the scopes the
configuration names ``attention_glue`` where it names any (Kimi: the
latent's split and norm)."""

import lm_trace


def reduce(run: dict):
    inside = lm_trace.attention_ms_per_step(run, pallas=False)
    around = lm_trace.part_ms_per_step(run, "attention_glue")
    if inside is None or around is None:
        return inside
    return inside + around
