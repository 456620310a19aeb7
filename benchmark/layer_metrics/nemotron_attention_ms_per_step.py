"""Kernels layer: device milliseconds per step in the one attention layer (the
configuration's ``attention`` scopes, ``l5_attn_*``: q, k, v, the flash
kernels at 32 / 2 heads of 128 without positions, o): the shared
``attention_ms_per_step`` reading under this cell's own name."""

from layer_metrics.attention_ms_per_step import reduce  # noqa: F401
