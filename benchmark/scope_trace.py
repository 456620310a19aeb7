"""A run's device trace, read by layer and pass: the join of what the chip
executed (``device_trace``'s operations) with the map the program publishes
for its own compiled step (``Engine.stats`` section ``step_scopes``:
``ops`` = {HLO instruction: "<scope>|<phase>"}, ``types`` = {scope: the
layer's TYPE, or "update" / "arena" / "sync"}).

The instruction is the key on both sides. A trace label reads
``<opcode> <instruction> <shape>`` on a TPU and is the instruction alone in
the ``--cpu-tiny`` rehearsal (``device_trace.label``). Every operation's
SELF time goes to its own instruction's scope, so the parts and the
``unmapped`` residual partition the chip's busy time exactly; nothing is
matched by shape or by name pattern, and an instruction the map does not
hold stays in the residual. A program that publishes no map (the parent of
the PR that added it) gives ``None`` everywhere and raises nothing.
"""

from __future__ import annotations

from typing import Dict, Optional

import device_trace

NET = "net"            # any scope whose type is a layer TYPE
ROLES = ("update", "arena", "sync")


def instruction(label: str) -> str:
    words = label.split(" ")
    return words[1] if len(words) > 1 else label


def published_map(run: dict) -> Optional[dict]:
    """The run's ``step_scopes`` section, or None when the program
    published none or an empty one (the jit path says why)."""
    section = ((run.get("stats") or {}).get("sections") or {}).get(
        "step_scopes") or {}
    return section if section.get("ops") else None


def parts(run: dict) -> Optional[dict]:
    """Milliseconds per traced step, mean over chips::

        {"busy_ms", "unmapped_ms",
         "by": {(role, layer type or "", phase): ms},
         "unmapped_ops": {label: ms}}

    ``role`` is ``net`` for a layer's operations (then the layer's TYPE and
    ``fwd`` / ``bwd`` follow) or ``update`` / ``arena`` / ``sync`` for the
    scopes around the layer graph. None without a trace or without a map.
    """
    devices = device_trace.traced_devices(run)
    scopes = published_map(run)
    if not devices or not scopes:
        return None
    ops, types = scopes["ops"], scopes.get("types", {})
    per = 1e6 * len(devices) * run["trace"]["steps"]     # ns -> ms/step/chip
    out = {"busy_ms": 0.0, "unmapped_ms": 0.0, "by": {}, "unmapped_ops": {}}
    for chip_ops in devices.values():
        for (label, _, _), own in zip(chip_ops,
                                      device_trace.self_times(chip_ops)):
            ms = own / per
            out["busy_ms"] += ms
            tagged = ops.get(instruction(label))
            if tagged is None:
                out["unmapped_ms"] += ms
                out["unmapped_ops"][label] = \
                    out["unmapped_ops"].get(label, 0.0) + ms
                continue
            scope, _, phase = tagged.rpartition("|")
            kind = types.get(scope, "")
            key = (kind, "", "misc") if kind in ROLES else (NET, kind, phase)
            out["by"][key] = out["by"].get(key, 0.0) + ms
    return out


def select(split: dict, roles=(NET,), layer_types=None,
           phases=None) -> float:
    """The part of one ``parts()`` result under the given roles, layer
    types and phases (None = any); 0.0 when nothing matched."""
    return sum(ms for (role, kind, phase), ms in split["by"].items()
               if role in roles
               and (layer_types is None or kind in layer_types)
               and (phases is None or phase in phases))


def ms_per_step(run: dict, **which) -> Optional[float]:
    """``select`` over the run's own ``parts``: what one reader reports.
    None without a trace or a map, 0.0 when the map is there and nothing
    matched."""
    split = parts(run)
    return None if split is None else select(split, **which)


def terms(split: dict) -> Dict[str, float]:
    """The identity the acceptance check reads, from one ``parts()``
    result: fwd + bwd + update + sync + unmapped = busy, ms per step."""
    return {"fwd_ms": select(split, phases=("fwd",)),
            "bwd_ms": select(split, phases=("bwd",)),
            "update_ms": select(split, roles=("update", "arena")),
            "sync_ms": select(split, roles=("sync",)),
            "unmapped_ms": split["unmapped_ms"],
            "busy_ms": split["busy_ms"]}


def summary(run: dict) -> Optional[Dict[str, float]]:
    split = parts(run)
    return None if split is None else terms(split)
