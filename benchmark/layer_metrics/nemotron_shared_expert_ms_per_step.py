"""Kernels layer: device milliseconds per step in the four shared experts of
3,712 and their sums with the routed part (the configuration's
``shared_expert`` scopes, ``l<i>_moe_shared_{up,relu,sq,down}`` and
``l<i>_moe_sum``: two products around a squared ReLU): the shared
``shared_expert_ms_per_step`` reading under this cell's own name."""

from layer_metrics.shared_expert_ms_per_step import reduce  # noqa: F401
