"""Kernels layer: device milliseconds per step, forward and backward, in the
vocabulary projection and the loss over it (the scopes the configuration
names under ``scopes.head``)."""

import lm_trace


def reduce(run: dict):
    if not run.get("lm"):
        return None
    return lm_trace.scopes_ms_per_step(run, run["lm"]["head_scopes"])
