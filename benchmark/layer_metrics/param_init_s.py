"""Graph layer: seconds initialising the parameters and the solver state: the
fillers' compiles and runs, to the point the leaves are ready (start-up span
``param_init``). Moves setup_s."""

import startup_spans


def reduce(run: dict):
    return startup_spans.seconds(run, "param_init")
