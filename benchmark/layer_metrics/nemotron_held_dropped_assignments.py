"""Graph layer: assignments to a held expert that no expert computed, the largest
over the window's displays and the four sparse layers (``l<i>_dropped``; 0:
the layer is dropless): the shared ``held_dropped_assignments`` reading under
this cell's own name."""

from layer_metrics.held_dropped_assignments import reduce  # noqa: F401
