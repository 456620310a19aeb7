"""Entry / compile-cache layer: seconds of backend compiles (or fetches from
the XLA cache) before the first step and outside the step's own load:
parameter init, rng, eval step, ``device_put`` helpers
(``startup.compile_s``). Moves setup_s."""

import startup_spans


def reduce(run: dict):
    return startup_spans.fact(run, "compile_s")
