"""Kernels layer: the stream's share of its roofline — the least time the
chip could take for what ``run["lm"]["hc_stream_per_step"]`` requires
(``flops_xing.hc_stream_step``: a sub-layer forward two reads and one write
of (T, n, C) and one read and one write of (T, C) in the stream's dtype, the
replay the same, the backward twice that, at the HBM peak; the count is the
same whether a kernel or XLA fusions implement the passes) over the device
time of the ``l<i>_hc_{a,f}_{map,read,write}`` scopes (the configuration's
``hc_stream``), in percent."""

import lm_trace


def reduce(run: dict):
    return lm_trace.roofline(run,
                             lm_trace.section(run).get("hc_stream_per_step"),
                             lm_trace.part_ms_per_step(run, "hc_stream"))
