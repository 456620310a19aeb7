"""Kernels layer: device milliseconds per step in latent attention's five
projections, all five blocks (the configuration's ``mla_proj`` scopes:
``l<i>_mla_{qa,qb,kva,kvb_k,kvb_v,o}``): forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "mla_proj")
