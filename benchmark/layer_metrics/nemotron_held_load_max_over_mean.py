"""Graph layer: assignments at the fullest of the 8 held experts over their mean,
mean over the window's displays and the four sparse layers
(``l<i>_expert_load``): the shared ``held_load_max_over_mean`` reading under
this cell's own name."""

from layer_metrics.held_load_max_over_mean import reduce  # noqa: F401
