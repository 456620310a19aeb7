"""Kernels layer: device milliseconds per step in the untied head over 16,384
rows and its loss (the configuration's ``head`` scopes,
``lm_{head,nll,loss}``): the shared ``head_ms_per_step`` reading under this
cell's own name."""

from layer_metrics.head_ms_per_step import reduce  # noqa: F401
