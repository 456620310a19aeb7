"""Graph layer: assignments at the fullest HELD expert over the held
experts' mean, from the step's own routing as the MOE layers publish it per
display (``*_expert_load``; mean over the window's displays and layers).
1.0 = the held experts share their rows evenly; a router that sends most of
them to one expert reads near the number held. The grouped matmuls' rows
follow the routing, whatever it is."""

import zaya_trace


def reduce(run: dict):
    return zaya_trace.mean_of(run, "expert_load")
