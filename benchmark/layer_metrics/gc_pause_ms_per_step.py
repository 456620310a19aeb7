"""Train-loop layer: host milliseconds per step inside Python's collector
(``gc_pause``, recorded through ``gc.callbacks`` on whichever thread the run
interrupted). A program that records ``gc_pause`` also marks every step
``step_done``; without those the metric is left out, not reported as 0."""

import host_spans


def reduce(run: dict):
    if not host_spans.named(run, "step_done"):
        return None
    return sum(e["dur"] for e in host_spans.named(run, "gc_pause")) \
        / 1e3 / run["steps"]
