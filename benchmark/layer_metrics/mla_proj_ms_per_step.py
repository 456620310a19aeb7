"""Kernels layer: device milliseconds per step in latent attention's five
projections, every block (the configuration's ``mla_proj`` scopes,
``<p>mla_{qa,qb,kva,kvb_k,kvb_v,o}``: GLM-4.7-Flash's six blocks, the
prediction module's among them; Xing4.0's five, ``l<i>_mla_*``): forward,
backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "mla_proj")
