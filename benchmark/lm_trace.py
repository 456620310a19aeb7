"""What the token cells' per-layer readers add to ``scope_trace``: ONE
module for every token configuration, keyed by what the run hands over and
never by a cell's name.

``run["lm"]`` is the token runners' section of ``layers``. A reader finds a
PART of the model by the configuration's own name pattern
(``run["lm"]["scopes"][<part>]``, a regular expression over whole scope
names: in a net unrolled over passes or layers one part is many scopes), the
work a kernel family REQUIRES under ``run["lm"][<need key>]`` (FLOPs and
bytes a step, from the configuration's ``flops_*.py``), and what the runner
read off the program's display rows under the keys the runners share
(``held_share``, ``expert_load``, ``dropped``, ...). All join the same three
things as ``scope_trace`` — trace operation -> HLO instruction -> the
program's published ``step_scopes`` map — and give None where the run has no
trace, no map, no ``lm`` section, or no such part or key: a metric is left
out of the line, it never reads 0 for want of something to read.
"""

from __future__ import annotations

import re
from typing import Callable, Optional

import device_trace
import scope_trace


def section(run: dict) -> dict:
    return run.get("lm") or {}


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


def part_ms_per_step(run: dict, part: str) -> Optional[float]:
    """Forward, backward and replay of the scopes whose whole NAME matches
    the configuration's pattern for ``part``; None where it names none."""
    scopes = section(run).get("scopes") or {}
    if part not in scopes:
        return None
    pattern = re.compile(scopes[part])
    return self_ms_per_step(
        run, lambda _, scope, __: pattern.fullmatch(scope) is not None)


def attention_ms_per_step(run: dict, kind: str = "",
                          pallas: Optional[bool] = None) -> Optional[float]:
    """The ATTENTION layers (TYPE), of one kind where the layers' names end
    in ``_attn_<kind>`` (``window`` / ``global``): all of their time, or
    (``pallas=True``) the flash kernels' Pallas custom calls alone, replays
    included, or (``pallas=False``) what lies OUTSIDE those calls: head
    split and merge, rotary positions, the key-value heads' repeat,
    rowsum(dO * O)."""
    suffix = f"_attn_{kind}" if kind else ""
    return self_ms_per_step(
        run, lambda label, scope, layer_type: layer_type == "ATTENTION"
        and scope.endswith(suffix)
        and pallas in (None, device_trace.is_pallas(label)))


def recomputed_ms_per_step(run: dict) -> Optional[float]:
    """The instructions the program's map lists as ``recomputed`` (forward
    ops a ``jax.checkpoint`` replays during backward); None where the map
    has no such list."""
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


def roofline(run: dict, need: Optional[dict], ms: Optional[float]
             ) -> Optional[float]:
    """The least time the chip could take for ``need`` (``flops`` over the
    bf16 peak or ``bytes`` over the HBM peak, whichever is larger) over
    ``ms``, in percent."""
    peaks = section(run).get("peaks")
    if not ms or not peaks or not need:
        return None
    least_s = max(need["flops"] / peaks["bf16_flops_per_s"],
                  need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)


def flops_util(run: dict, flops: Optional[float], ms: Optional[float]
               ) -> Optional[float]:
    """``flops`` a step over ``ms`` x the chip's bf16 peak, in percent."""
    if not ms or flops is None or not run.get("peak_flops_per_s"):
        return None
    return 100.0 * flops / (ms / 1e3 * run["peak_flops_per_s"])


def mean_of(run: dict, key: str) -> Optional[float]:
    """Mean of what the runner read off the display rows under ``key``."""
    values = section(run).get(key)
    return sum(values) / len(values) if values else None


def window_visited_over_live(run: dict) -> Optional[float]:
    """Visited over live programs a head, summed over the three kernels,
    from the window layers' ``kernel_routes`` note (``fwd 1024x1024 21/24,
    dq ..., dkv ...``): 1.0 where only live blocks are visited."""
    routes = [r for r in section(run).get("kernel_routes") or ()
              if "window" in r and "pallas_flash" in r]
    pairs = [(int(a), int(b)) for r in routes
             for a, b in re.findall(r"\b(\d+)/(\d+)\b", r)]
    live = sum(a for a, _ in pairs)
    return sum(b for _, b in pairs) / live if live else None
