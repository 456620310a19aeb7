"""Kernels layer: device milliseconds per step in the delta-rule mixers (the
configuration's ``delta`` scopes: every ``l<i>_kda_*`` / ``l<i>_gdn_*`` layer —
projections, short convolutions, norms, the decay, the scan, the output gate):
forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "delta")
