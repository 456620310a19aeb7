"""Entry / compile-cache layer: seconds a warm start spends bringing the
serialized train step back from ``aot/`` (start-up spans ``aot_read`` +
``aot_unpack`` + ``aot_deserialize``; 0 on a run that compiled). Moves setup_s."""

import startup_spans


def reduce(run: dict):
    return startup_spans.seconds(run, "aot_read", "aot_unpack",
                                 "aot_deserialize")
