"""Kernels layer: device milliseconds per step in Nemotron-H's four Mamba-2
layers (the configuration's ``ssd`` scopes, every ``l<i>_ssd_*`` layer: the
two projections, the splits, the short convolution over 6,144 channels, the
step and the decay, the grouped scan, the gate, the grouped output norm): the
shared ``ssd_ms_per_step`` reading under this cell's own name."""

from layer_metrics.ssd_ms_per_step import reduce  # noqa: F401
