"""Entry layer: share of the program's own start-up stretch (``cli_setup``'s
start to ``first_step``'s end) that lies under a top-level start-up span
(``startup.coverage``): the timeline's own honesty, as ``scope_coverage`` is
for the step. Moves setup_s."""

import startup_spans


def reduce(run: dict):
    cover = startup_spans.fact(run, "coverage")
    return None if cover is None else 100.0 * cover
