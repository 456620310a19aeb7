"""Train-loop layer: tokens of all steps completed in the measured window over
its seconds, per chip, in ``nemotron_h.e8of128.pack8k`` (2 sequences of 8,192
a step): the shared ``tokens_per_s_per_chip`` reading under this cell's own
name, because an accepted entry's ``workloads`` list is a ``benchmark`` PR's
to extend (PERF.md 63a, 64)."""

from layer_metrics.tokens_per_s_per_chip import reduce  # noqa: F401
