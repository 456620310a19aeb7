"""Kernels layer: the grouped scan's share of its roofline — the least time for
what ``run["lm"]["ssd_scan_per_step"]`` requires
(``flops_nemotron.ssd_scan_step``: 3 passes of 2 H P N MACs a token at the
published 64 x 64 x 128 over the bf16 peak, or x, y, EIGHT groups of B and C,
dt, a and their gradients once over the HBM peak, whichever is larger) over
``nemotron_ssd_scan_ms_per_step``, in percent: the shared
``ssd_scan_roofline`` reading under this cell's own name."""

from layer_metrics.ssd_scan_roofline import reduce  # noqa: F401
