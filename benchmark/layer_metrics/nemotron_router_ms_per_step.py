"""Kernels layer: device milliseconds per step in the four MOE_ROUTER layers (the
configuration's ``router`` scopes, ``l<i>_moe_router``: sigmoid scores over
128 experts in f32, top-6 by score + bias, the balancing rule): the shared
``router_ms_per_step`` reading under this cell's own name."""

from layer_metrics.router_ms_per_step import reduce  # noqa: F401
