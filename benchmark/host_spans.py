"""The spans the program records on its own threads during the measured
window (``run["spans"]``: Chrome trace events with ``name``, ``ts`` and
``dur`` in microseconds, ``tid``, ``args``), as the input-layer and
train-loop readers take them. A program that records none of a kind its
every batch, step or display would have left (the parent of the PR that
added them) gives ``None``: the metric is left out, nothing raises."""

from __future__ import annotations

from typing import List, Optional


def named(run: dict, name: str) -> List[dict]:
    return [e for e in run.get("spans") or () if e["name"] == name]


def mean_ms(run: dict, name: str) -> Optional[float]:
    """Mean duration of the window's ``name`` spans, or None without any."""
    durs = [e["dur"] for e in named(run, name) if e.get("ph") == "X"]
    return sum(durs) / len(durs) / 1e3 if durs else None
