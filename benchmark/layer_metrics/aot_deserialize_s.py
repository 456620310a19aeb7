"""Entry / compile-cache layer: the runtime's share of a load, jax's
``deserialize_and_load`` alone (start-up span ``aot_deserialize``; 0 on a run
that compiled). Moves setup_s."""

import startup_spans


def reduce(run: dict):
    return startup_spans.seconds(run, "aot_deserialize")
