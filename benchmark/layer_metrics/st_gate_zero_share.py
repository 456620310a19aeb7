"""Graph layer: the share of the held experts' live rows' gate
pre-activations that are <= 0, as the MOE layers count it in the chunk body
and publish it per display (``*_gate_zero_share``; mean over the window's
displays and layers), in percent: what ReGLU zeroes, the sparsity a kernel
that skipped dead units could use. 50 = a fresh, symmetric gate."""

import lm_trace


def reduce(run: dict):
    share = lm_trace.mean_of(run, "gate_zero_share")
    return None if share is None else 100.0 * share
