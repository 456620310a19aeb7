"""Train-loop layer: seconds in ``Engine.__init__`` (start-up span
``engine_build``: pipelines, nets, step builders, parameter init). Moves
setup_s."""

import startup_spans


def reduce(run: dict):
    return startup_spans.seconds(run, "engine_build")
