"""Entry / compile-cache layer: what the program's own attribution costs every
start: the executable's text and the passes over it, and the step's scope map
(start-up spans ``step_text`` + ``scope_map``). Moves setup_s."""

import startup_spans


def reduce(run: dict):
    return startup_spans.seconds(run, "step_text", "scope_map")
