"""Kernels layer: device milliseconds per step the five ATTENTION layers spend
OUTSIDE their Pallas calls (the head split and merge of the head-major form,
the rotation of the shared key part and of q's tails by YaRN's angles, the
shared part's hand-over to the 32 heads, ``rowsum(dO * O)``), plus the
configuration's ``attention_glue`` scopes (the query latent's norm, the
key-value latent's split and norm)."""

import lm_trace


def reduce(run: dict):
    inside = lm_trace.attention_ms_per_step(run, pallas=False)
    around = lm_trace.part_ms_per_step(run, "attention_glue")
    if inside is None or around is None:
        return inside
    return inside + around
