"""Kernels layer: device milliseconds per step in what stands between the Mamba-2
layers' projections and their scans (the configuration's ``ssd_glue`` scopes:
the two splits, the convolution, the decay, the gate, the grouped norm): the
shared ``ssd_glue_ms_per_step`` reading under this cell's own name."""

from layer_metrics.ssd_glue_ms_per_step import reduce  # noqa: F401
