"""What the Olmo-Hybrid cell's per-layer readers (``layer_metrics/gdn_*``,
``oh_*``) add to ``lm_trace`` / ``looplm_trace``: device time of the scopes
whose NAME matches a pattern the run hands over (``run["lm"]["scopes"]``,
the configuration's), marked by the key ``olmo_hybrid`` in ``run["lm"]`` so
that no other cell's run reads as this one; the Pallas calls' time inside
the full layers' ATTENTION scopes; a part's share of its roofline or of the
compute peak. None where there is no trace, no map, or a run that is not
this cell's — a program from before the model publishes no such scopes."""

from __future__ import annotations

from typing import Optional

import device_trace
import kimi_trace
import lm_trace
import looplm_trace


def is_ours(run: dict) -> bool:
    return bool((run.get("lm") or {}).get("olmo_hybrid"))


def part_ms_per_step(run: dict, part: str) -> Optional[float]:
    if not is_ours(run):
        return None
    return looplm_trace.pattern_ms_per_step(run, part)


def roofline(run: dict, need_key: str, ms: Optional[float]
             ) -> Optional[float]:
    """``kimi_trace.roofline`` (the least time for ``run["lm"][need_key]``,
    FLOPs over the bf16 peak or bytes over the HBM peak, over ``ms``, in
    percent), in this cell's runs only."""
    return kimi_trace.roofline(run, need_key, ms) if is_ours(run) else None


def flops_util(run: dict, part: str, ms: Optional[float]) -> Optional[float]:
    """``run["lm"]["flops_per_step"][part]`` over ``ms`` x the chip's bf16
    peak, in percent."""
    if not is_ours(run) or not ms or not run.get("peak_flops_per_s"):
        return None
    return 100.0 * run["lm"]["flops_per_step"][part] \
        / (ms / 1e3 * run["peak_flops_per_s"])


def flash_ms_per_step(run: dict) -> Optional[float]:
    """The Pallas custom calls inside the full layers' ATTENTION scopes,
    replays included."""
    if not is_ours(run):
        return None
    return lm_trace.self_ms_per_step(
        run, lambda label, _, layer_type: layer_type == "ATTENTION"
        and device_trace.is_pallas(label))
