"""Device layer: seconds from the first dispatch of the train step until its
result is ready, the program's load onto the chip included (start-up span
``first_step``; not the runners' fact ``first_step_s``, which holds the step
load too). Moves setup_s."""

import startup_spans


def reduce(run: dict):
    return startup_spans.seconds(run, "first_step")
