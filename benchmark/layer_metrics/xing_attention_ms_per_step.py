"""Kernels layer: device milliseconds per step in Xing4.0's latent attention,
all five blocks (the configuration's ``attention`` scopes, the whole
``l<i>_mla_*`` block: the five projections, the two latents' norms and split,
the head split, the rotation by YaRN's angles, the shared key part's
hand-over to the heads and the flash kernels at heads of 192 / 128,
head-major): forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "attention")
