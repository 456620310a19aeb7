"""Graph layer: device milliseconds per step in forward operations that
activation remat runs a second time, during backward, in this cell's ten
checkpointed units (nine layers and the head): the shared
``recompute_ms_per_step`` reading under this cell's own name."""

from layer_metrics.recompute_ms_per_step import reduce  # noqa: F401
