"""Input layer: milliseconds the prefetcher thread waits, after the
``device_put`` calls of one batch have returned (``producer_h2d``,
``h2d_ms_per_batch``), until the batch's arrays are on the device, with
nothing of an earlier batch still on the link (mean ``producer_h2d_land``;
recorded for a sample of the batches, only while the recorder is
enabled)."""

import host_spans


def reduce(run: dict):
    return host_spans.mean_ms(run, "producer_h2d_land")
