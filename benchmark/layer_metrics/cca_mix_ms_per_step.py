"""Kernels layer: of ``cca_ms_per_step``, the memory-bound work no other
cell has — the ``l<i>_cca_*`` scopes (shift, value concat, depthwise and
grouped convolution, q-k mean, L2 norm with its temperature) plus the
ATTENTION layers' time outside their Pallas calls (rotary positions on half
a head, head split and merge, the key-value heads' repeat)."""

import lm_trace


def reduce(run: dict):
    mix = lm_trace.part_ms_per_step(run, "cca_mix")
    glue = lm_trace.attention_ms_per_step(run, pallas=False)
    if mix is None or glue is None:
        return None
    return mix + glue
