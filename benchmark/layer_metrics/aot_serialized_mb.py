"""Entry / compile-cache layer: megabytes of the serialized train step, unpacked
(``startup.aot_bytes_serialized``: what a load has to deserialize; 0 when
nothing was stored or loaded). Moves setup_s."""

import startup_spans


def reduce(run: dict):
    size = startup_spans.fact(run, "aot_bytes_serialized")
    return None if size is None else size / 1e6
