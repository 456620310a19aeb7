"""What the looped-LM cell's per-layer readers add to ``lm_trace``: device
time of the scopes whose NAME matches a pattern the run hands over
(``run["lm"]["scopes"]``: the FFN's layers, the exit heads' layers — in a
net unrolled over passes one part is many scopes, ``p<t>_l<i>_ffn_*``), and
of the instructions the program's map lists as ``recomputed`` (forward ops
that a ``jax.checkpoint`` replays during backward). Same join as
``scope_trace``; None where there is no trace, no map, no ``lm`` section with
``scopes``, or (for the recomputation) a map without the list — a program
from before it."""

from __future__ import annotations

import re
from typing import Optional

import device_trace
import lm_trace
import scope_trace


def pattern_ms_per_step(run: dict, part: str) -> Optional[float]:
    scopes = (run.get("lm") or {}).get("scopes") or {}
    if part not in scopes:
        return None
    pattern = re.compile(scopes[part])
    return lm_trace.self_ms_per_step(
        run, lambda _, scope, __: pattern.fullmatch(scope) is not None)


def recomputed_ms_per_step(run: dict) -> Optional[float]:
    devices = device_trace.traced_devices(run)
    scopes = scope_trace.published_map(run)
    if not devices or not scopes or "recomputed" not in scopes:
        return None
    replayed = set(scopes["recomputed"])
    per = 1e6 * len(devices) * run["trace"]["steps"]     # ns -> ms/step/chip
    return sum(own / per
               for chip_ops in devices.values()
               for (label, _, _), own in zip(
                   chip_ops, device_trace.self_times(chip_ops))
               if scope_trace.instruction(label) in replayed)
