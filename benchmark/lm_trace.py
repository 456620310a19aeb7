"""What the token-model cell's per-layer readers add to ``scope_trace``:
device time by scope NAME (``scope_trace`` sums by layer TYPE, which cannot
tell the head's INNER_PRODUCT from the projections'), and the Pallas
kernels' time inside the layers of one TYPE. Both read the same join —
trace operation -> HLO instruction -> the program's published
``step_scopes`` map — and give None where there is no trace, no map, or no
``lm`` section in the run (a program that lacks the token layers)."""

from __future__ import annotations

from typing import Callable, Optional

import device_trace
import scope_trace


def self_ms_per_step(run: dict, keep: Callable[[str, str, str], bool]
                     ) -> Optional[float]:
    """Milliseconds per traced step, mean over chips, of the operations for
    which ``keep(label, scope name, scope type)`` holds (self times, so the
    parts of a partition add up)."""
    devices = device_trace.traced_devices(run)
    scopes = scope_trace.published_map(run)
    if not devices or not scopes or not run.get("lm"):
        return None
    ops, types = scopes["ops"], scopes.get("types", {})
    per = 1e6 * len(devices) * run["trace"]["steps"]     # ns -> ms/step/chip
    ms = 0.0
    for chip_ops in devices.values():
        for (label, _, _), own in zip(chip_ops,
                                      device_trace.self_times(chip_ops)):
            tagged = ops.get(scope_trace.instruction(label))
            if tagged is None:
                continue
            scope = tagged.rpartition("|")[0]
            if keep(label, scope, types.get(scope, "")):
                ms += own / per
    return ms


def scopes_ms_per_step(run: dict, names) -> Optional[float]:
    names = set(names)
    return self_ms_per_step(run, lambda _, scope, __: scope in names)


def pallas_ms_per_step(run: dict, layer_type: str) -> Optional[float]:
    return self_ms_per_step(
        run, lambda label, _, kind: kind == layer_type
        and device_trace.is_pallas(label))
