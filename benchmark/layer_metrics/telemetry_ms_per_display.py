"""Train-loop layer: host milliseconds per display boundary in the Engine's
telemetry flush (mean ``telemetry_dump``: the stats.yaml write)."""

import host_spans


def reduce(run: dict):
    return host_spans.mean_ms(run, "telemetry_dump")
