"""Kernels layer: the flash kernels' share of their roofline in the one attention
layer (32 query heads on 2 key-value heads of 128, token-major, S 8,192;
``flops_nemotron.flash_attention_step``): the shared
``flash_attention_roofline`` reading under this cell's own name."""

from layer_metrics.flash_attention_roofline import reduce  # noqa: F401
