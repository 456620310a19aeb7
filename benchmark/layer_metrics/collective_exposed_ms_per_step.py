"""Step builder / sync layer: the part of collective_ms_per_step during which
no other operation runs on that chip — what a better overlap could hide."""

from layer_metrics.collective_ms_per_step import per_step


def reduce(run: dict):
    return per_step(run, 1)
