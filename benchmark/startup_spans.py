"""The start-up timeline the program keeps on its own recorder until its
first train step is done, as it summarises it in stats section ``startup``
(``run["stats"]["sections"]["startup"]``: ``spans`` = seconds by span name,
the facts noted at the boundaries, ``coverage``), for the readers of the
metrics under ``setup_s``. A program without the section (the parent of the
PR that added it) gives ``None``: the metric is left out, nothing raises."""

from __future__ import annotations

from typing import Optional


def section(run: dict) -> Optional[dict]:
    return (run.get("stats") or {}).get("sections", {}).get("startup")


def seconds(run: dict, *names: str) -> Optional[float]:
    """Seconds under the start-up spans ``names`` (0 for one that did not
    happen in this run), or None without the section."""
    sec = section(run)
    if sec is None:
        return None
    return float(sum(sec.get("spans", {}).get(n, 0.0) for n in names))


def fact(run: dict, name: str) -> Optional[float]:
    """A number the program noted at a start-up boundary (0 when this run
    had no such boundary), or None without the section."""
    sec = section(run)
    return None if sec is None else float(sec.get(name, 0.0))


def timeline(run: dict, name: str) -> Optional[dict]:
    """The top-level start-up span ``name`` as the program's ``timeline``
    has it (``at_s`` from the OS's start of the process, ``dur_s``), or None
    without the section or the row."""
    return ((section(run) or {}).get("timeline") or {}).get(name)
