"""Graph layer: the share of the held experts' live rows' pre-activations
(up x: there is no gate) that are <= 0, as the MOE layers count it where the
pre-activation is at hand and publish it per display (``*_act_zero_share``;
mean over the window's displays and the four sparse layers), in percent: what
the squared ReLU zeroes, the sparsity a kernel that skipped dead units could
use in the down product. 50 = a fresh, symmetric up projection."""

import lm_trace


def reduce(run: dict):
    share = lm_trace.mean_of(run, "act_zero_share")
    return None if share is None else 100.0 * share
