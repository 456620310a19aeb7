"""Entry / compile-cache layer: seconds in ``lowered.compile()``, a backend
compile or the XLA cache's answer (start-up span ``step_compile``; 0 on a run
that loaded the step). Moves setup_s."""

import startup_spans


def reduce(run: dict):
    return startup_spans.seconds(run, "step_compile")
