"""Graph layer: the prediction module's loss over the main loss, mean over the
window's displays (the display rows' ``mtp_loss`` / ``lm_loss``,
``run["lm"]["mtp_loss_over_main"]``). Near 1 on fresh weights, where neither
head knows anything; it rises as the main loss falls first."""

import lm_trace


def reduce(run: dict):
    return lm_trace.mean_of(run, "mtp_loss_over_main")
