"""Kernels layer: device milliseconds per step the six ATTENTION layers spend
OUTSIDE their Pallas calls (the rotation of the shared key part and of q's
tails, the shared part's hand-over to the 20 heads along the lanes and its
transpose, ``rowsum(dO * O)``), plus the configuration's ``attention_glue``
scopes (the query latent's norm, the key-value latent's split and norm): what
ROADMAP M4's remainder costs. ``attention_glue_ms_per_step`` under a name of
this cell's own."""

import lm_trace


def reduce(run: dict):
    inside = lm_trace.attention_ms_per_step(run, pallas=False)
    around = lm_trace.part_ms_per_step(run, "attention_glue")
    if inside is None or around is None:
        return inside
    return inside + around
