"""Input layer: seconds opening the data sources and starting their reader
threads (start-up span ``pipeline_open``). Moves setup_s."""

import startup_spans


def reduce(run: dict):
    return startup_spans.seconds(run, "pipeline_open")
