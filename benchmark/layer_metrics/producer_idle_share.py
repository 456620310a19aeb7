"""Input layer: share of the measured window the reader thread spent blocked
on a full queue (``producer_queue_full`` on the thread that records
``producer_read``). Its headroom: 0 = the reader never waited, the run is
input-bound."""

import host_spans


def reduce(run: dict):
    readers = {e["tid"] for e in host_spans.named(run, "producer_read")}
    if not readers:
        return None
    blocked = sum(e["dur"] for e in host_spans.named(run,
                                                     "producer_queue_full")
                  if e["tid"] in readers)
    return 100.0 * blocked / 1e6 / run["window_s"] / len(readers)
