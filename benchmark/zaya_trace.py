"""What the ZAYA cell's per-layer readers add to ``lm_trace`` /
``looplm_trace``: device time of the scopes whose NAME matches a pattern the
run hands over (``run["lm"]["scopes"]``, the configuration's), marked by the
key ``zaya`` in ``run["lm"]`` so that no other cell's run reads as this one;
and the ATTENTION layers' time OUTSIDE their Pallas calls (head split and
merge, rotary positions, the key-value heads' repeat, ``rowsum(dO * O)``).
None where there is no trace, no map, or a run that is not this cell's — a
program from before the model publishes no such scopes."""

from __future__ import annotations

from typing import Optional

import device_trace
import lm_trace
import looplm_trace


def is_ours(run: dict) -> bool:
    return bool((run.get("lm") or {}).get("zaya"))


def part_ms_per_step(run: dict, part: str) -> Optional[float]:
    if not is_ours(run):
        return None
    return looplm_trace.pattern_ms_per_step(run, part)


def attention_glue_ms_per_step(run: dict) -> Optional[float]:
    if not is_ours(run):
        return None
    return lm_trace.self_ms_per_step(
        run, lambda label, _, kind: kind == "ATTENTION"
        and not device_trace.is_pallas(label))


def mean_of(run: dict, key: str) -> Optional[float]:
    values = (run.get("lm") or {}).get(key) if is_ours(run) else None
    return sum(values) / len(values) if values else None
