"""Kernels layer: device milliseconds per step in what stands around the delta
rule's scan and projections (the configuration's ``delta_glue`` scopes: short
convolutions, L2 norms, the decay's form, sigmoids, the output norm and gate):
forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "delta_glue")
