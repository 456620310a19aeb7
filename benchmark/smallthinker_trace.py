"""What the SmallThinker cell's per-layer readers (``layer_metrics/st_*``)
add to ``lm_trace`` / ``looplm_trace``: device time of the scopes whose NAME
matches a pattern the run hands over (``run["lm"]["scopes"]``, the
configuration's), marked by the key ``smallthinker`` in ``run["lm"]`` so
that no other cell's run reads as this one; the Pallas calls' time inside
the ATTENTION layers of one kind (window or global, told apart by the
layer's name); the ATTENTION layers' time OUTSIDE their Pallas calls; a
flash kernel family's share of its roofline; the live / visited programs the
program's ``kernel_routes`` state; and what the runner read off the display
rows. None where there is no trace, no map, or a run that is not this
cell's — a program from before the model publishes no such scopes."""

from __future__ import annotations

import re
from typing import Optional

import device_trace
import lm_trace
import looplm_trace


def is_ours(run: dict) -> bool:
    return bool((run.get("lm") or {}).get("smallthinker"))


def part_ms_per_step(run: dict, part: str) -> Optional[float]:
    if not is_ours(run):
        return None
    return looplm_trace.pattern_ms_per_step(run, part)


def attention_ms_per_step(run: dict, kind: str) -> Optional[float]:
    """The whole ATTENTION scopes named ``*_attn_<kind>``."""
    if not is_ours(run):
        return None
    suffix = f"_attn_{kind}"
    return lm_trace.self_ms_per_step(
        run, lambda _, scope, layer_type: layer_type == "ATTENTION"
        and scope.endswith(suffix))


def flash_ms_per_step(run: dict, kind: str) -> Optional[float]:
    """The Pallas custom calls inside the ATTENTION layers named
    ``*_attn_<kind>`` (``window`` / ``global``), replays included."""
    if not is_ours(run):
        return None
    suffix = f"_attn_{kind}"
    return lm_trace.self_ms_per_step(
        run, lambda label, scope, layer_type: layer_type == "ATTENTION"
        and scope.endswith(suffix) and device_trace.is_pallas(label))


def flash_roofline(run: dict, kind: str) -> Optional[float]:
    """The least time the chip could take for what the ``kind`` layers'
    flash kernels require (``flops_smallthinker.flash_attention_step``:
    FLOPs over the bf16 peak or bytes over the HBM peak, whichever is
    larger) over ``flash_ms_per_step``, in percent."""
    lm = run.get("lm") or {}
    ms = flash_ms_per_step(run, kind)
    if not ms or not lm.get("peaks"):
        return None
    need = lm["flash_per_step"][kind]
    least_s = max(need["flops"] / lm["peaks"]["bf16_flops_per_s"],
                  need["bytes"] / lm["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)


def attention_glue_ms_per_step(run: dict) -> Optional[float]:
    if not is_ours(run):
        return None
    return lm_trace.self_ms_per_step(
        run, lambda label, _, layer_type: layer_type == "ATTENTION"
        and not device_trace.is_pallas(label))


def published(run: dict, key: str):
    """What the runner read off the program's own counters and display rows
    under ``key`` (``run["lm"]``), None in a run that is not this cell's."""
    return (run.get("lm") or {}).get(key) if is_ours(run) else None


def mean_of(run: dict, key: str) -> Optional[float]:
    values = published(run, key)
    return sum(values) / len(values) if values else None


def window_visited_over_live(run: dict) -> Optional[float]:
    """Visited over live programs a head, summed over the three kernels,
    from the window layers' ``kernel_routes`` note (``fwd 1024x1024 70/80,
    dq ..., dkv ...``): 1.0 where only live blocks are visited."""
    routes = [r for r in published(run, "kernel_routes") or ()
              if "window" in r and "pallas_flash" in r]
    pairs = [(int(a), int(b)) for r in routes
             for a, b in re.findall(r"\b(\d+)/(\d+)\b", r)]
    live = sum(a for a, _ in pairs)
    return sum(b for _, b in pairs) / live if live else None
