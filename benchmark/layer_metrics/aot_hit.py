"""Entry / compile-cache layer: 1 when the train step was loaded from ``aot/``
(``startup.route`` is ``loaded``), else 0; the median over runs says which
route the warm runs took. Moves setup_s."""

import startup_spans


def reduce(run: dict):
    sec = startup_spans.section(run)
    return None if sec is None else float(sec.get("route") == "loaded")
