"""Kernels layer: device milliseconds per step in the stream's MAPPINGS (the
configuration's ``hc_map`` scopes, ``l<i>_hc_{a,f}_map``: the one statistic
over a token's 14,336 values, the (24, 14336) projection, the two sigmoids,
the 20 Sinkhorn iterations with the tokens along the lanes and the
transpose to the token-major coefficients): forward, backward (the loop's
too) and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "hc_map")
