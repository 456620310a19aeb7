"""Graph layer: the share of all expert assignments that went to one of the 8
experts this chip holds of 128, mean over the window's displays and the four
sparse layers, in percent (even: 6.25): the shared ``held_assignment_share``
reading under this cell's own name."""

from layer_metrics.held_assignment_share import reduce  # noqa: F401
