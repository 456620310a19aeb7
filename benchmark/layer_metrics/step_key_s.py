"""Entry / compile-cache layer: seconds of the step load before the store is
asked: imports, the hash of the package's sources, the AOT key (start-up
span ``step_key``). With it the parts sum to ``step_load_s``: ``aot_load_s``
+ ``step_trace_lower_s`` + ``step_compile_s`` + the store + ``step_text_s`` +
this. Moves setup_s."""

import startup_spans


def reduce(run: dict):
    return startup_spans.seconds(run, "step_key")
