"""Entry / compile-cache layer: seconds tracing and lowering the train step
(start-up span ``step_trace_lower``; 0 on a run that loaded it). Moves
setup_s."""

import startup_spans


def reduce(run: dict):
    return startup_spans.seconds(run, "step_trace_lower")
