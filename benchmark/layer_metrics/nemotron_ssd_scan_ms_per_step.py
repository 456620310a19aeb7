"""Kernels layer: device milliseconds per step in the four SSD_SCAN layers alone
(``l<i>_ssd_scan``: the Pallas kernels with eight groups of B / C, a group a
program, forward, backward and replay): the shared ``ssd_scan_ms_per_step``
reading under this cell's own name."""

from layer_metrics.ssd_scan_ms_per_step import reduce  # noqa: F401
