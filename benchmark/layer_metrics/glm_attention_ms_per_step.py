"""Kernels layer: device milliseconds per step in GLM-4.7-Flash's latent
attention, all six blocks (the configuration's ``attention`` scopes, the
whole ``<p>mla_*`` block: the five projections, the two latents' norms and
split, the rotation, the shared key part's hand-over to the heads and the
flash kernels at heads of 256 / 256, token-major): forward, backward and
replay. ``attention_ms_per_step`` under a name of this cell's own (PERF.md
section 7: the merge is a benchmark PR's)."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "attention")
