"""Kernels layer: device milliseconds per step in the residual STREAM of
Xing4.0's hyper-connections (the configuration's ``hc`` scopes: ``hc_start``,
``hc_end`` and every sub-layer's ``l<i>_hc_{a,f}_{map,read,write}``):
forward, backward and replay. The largest share of the step outside the
matmuls and the flash kernels."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "hc")
