"""Input layer: host milliseconds the prefetcher thread spends handing one
batch to the runtime (mean ``producer_h2d``: the ``device_put`` calls, which
return before the copy has landed on the device)."""

import host_spans


def reduce(run: dict):
    return host_spans.mean_ms(run, "producer_h2d")
