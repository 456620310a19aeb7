"""The stall ledger the program makes of a measured window on its own
recorder (``runtime/spans.py`` ``stall_ledger``; stats section ``stalls``,
``run["stats"]["sections"]["stalls"]``, published when the window's
``Engine.train`` call returns): every late step of the window with a cause
from inside the program, as the train-loop readers of ``stall_*`` take it.
A program without the section (the parent of the PR that added it, a
window that ran outside ``Engine.train``) gives ``None``: the metric is
left out, nothing raises."""

from __future__ import annotations

from typing import Optional


def section(run: dict) -> Optional[dict]:
    """The section, if it holds a step: a ledger of no step says nothing."""
    sec = (run.get("stats") or {}).get("sections", {}).get("stalls")
    return sec if sec and sec.get("steps") else None


def host_lost_ms(sec: dict) -> float:
    """What the window lost to anything but a step that was long on the
    chip with the host waiting (cause ``device``)."""
    return float(sum(ms for cause, ms in
                     (sec.get("lost_ms_by_cause") or {}).items()
                     if cause != "device"))


def per_step(run: dict, ms) -> Optional[float]:
    """``ms(section)`` over the section's steps; None without the section."""
    sec = section(run)
    return None if sec is None else float(ms(sec)) / sec["steps"]
