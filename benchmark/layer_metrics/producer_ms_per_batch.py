"""Input layer: host milliseconds the reader thread takes to make one batch
(mean ``producer_read``: index draw, read, transform). Batch size over this
is what one pipeline can feed, whatever the step asks for."""

import host_spans


def reduce(run: dict):
    return host_spans.mean_ms(run, "producer_read")
