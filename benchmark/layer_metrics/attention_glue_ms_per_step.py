"""Kernels layer: device milliseconds per step the ATTENTION layers spend
OUTSIDE their Pallas calls (head split and merge, rotary positions, the
key-value heads' repeat, ``rowsum(dO * O)``), plus the scopes the
configuration names ``attention_glue`` where it names any (Kimi: the
latent's split and norm; GLM-4.7-Flash and Xing4.0: the query latent's norm,
the key-value latent's split and norm). In the two latent forms the glue
inside the layers is the rotation of the shared key part and of q's tails
(Xing4.0: by YaRN's angles) and the shared part's hand-over to the 20 / 32
heads (GLM: along the lanes, and its transpose); Xing4.0's head-major form
adds the head split and merge: what ROADMAP M4's remainder costs."""

import lm_trace


def reduce(run: dict):
    inside = lm_trace.attention_ms_per_step(run, pallas=False)
    around = lm_trace.part_ms_per_step(run, "attention_glue")
    if inside is None or around is None:
        return inside
    return inside + around
