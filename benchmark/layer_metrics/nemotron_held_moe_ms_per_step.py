"""Kernels layer: device milliseconds per step in the four MOE layers of UNGATED
experts of which this chip holds 8 of 128 (the configuration's ``held_moe``
scopes, ``l<i>_moe_experts``: the sort, two grouped matmuls a trip over the
live rows in chunks, the combine): the shared ``held_moe_ms_per_step`` reading
under this cell's own name."""

from layer_metrics.held_moe_ms_per_step import reduce  # noqa: F401
