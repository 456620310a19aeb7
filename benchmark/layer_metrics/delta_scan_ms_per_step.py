"""Kernels layer: device milliseconds per step in the delta rule's chunked scan
alone (the configuration's ``delta_scan`` scopes, ``l<i>_kda_scan`` /
``l<i>_gdn_scan``): forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "delta_scan")
