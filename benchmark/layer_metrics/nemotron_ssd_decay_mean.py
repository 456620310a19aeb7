"""Graph layer: the mean of exp(a) over tokens, heads, the four Mamba-2 layers
and the window's displays (KDA_DECAY's scalar top ``l<i>_ssd_decay_mean``):
what share of a state a token keeps; the shared ``ssd_decay_mean`` reading
under this cell's own name."""

from layer_metrics.ssd_decay_mean import reduce  # noqa: F401
