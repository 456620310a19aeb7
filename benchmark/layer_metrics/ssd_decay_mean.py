"""Graph layer: the mean of exp(a) over tokens, heads, the nine Mamba-2 layers
and the window's displays (KDA_DECAY's scalar top ``l<i>_ssd_decay_mean`` in
the Engine's metric rows, ``run["lm"]["ssd_decay_mean"]``): what share of a
state a token keeps. Near 1 the state forgets nothing, near 0 everything."""

import lm_trace


def reduce(run: dict):
    return lm_trace.mean_of(run, "ssd_decay_mean")
