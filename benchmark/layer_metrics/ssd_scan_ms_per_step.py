"""Kernels layer: device milliseconds per step in Mamba-2's selective scan
alone (the configuration's ``ssd_scan`` scopes, ``l<i>_ssd_scan``: whichever
arm runs, the Pallas kernels or the ``jax.numpy`` chunked form): forward,
backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "ssd_scan")
