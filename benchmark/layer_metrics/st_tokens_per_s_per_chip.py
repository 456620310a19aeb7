"""Train-loop layer: tokens of all steps completed in the measured window
over its seconds, per chip (host clock; the window opens and closes on a hard
sync): the cell's throughput in its own unit."""

import smallthinker_trace


def reduce(run: dict):
    if not smallthinker_trace.is_ours(run):
        return None
    return run["steps"] * run["batch_per_chip"] * run["lm"]["seq_len"] \
        / run["window_s"]
