"""What the Kimi-Linear cell's per-layer readers add to ``lm_trace`` /
``looplm_trace``: device time of the scopes whose NAME matches a pattern the
run hands over (``run["lm"]["scopes"]``, the configuration's), marked by the
key ``kimi`` in ``run["lm"]`` so that no other cell's run reads as this one;
the Pallas calls' time inside the MLA layers' ATTENTION scopes and those
scopes' time outside them; the recurrence's and the flash kernels' share of
their rooflines; what the runner read off the program's counters and display
rows. None where there is no trace, no map, or a run that is not this
cell's — a program from before the model publishes no such scopes."""

from __future__ import annotations

from typing import Optional

import device_trace
import lm_trace
import looplm_trace


def is_ours(run: dict) -> bool:
    return bool((run.get("lm") or {}).get("kimi"))


def part_ms_per_step(run: dict, part: str) -> Optional[float]:
    if not is_ours(run):
        return None
    return looplm_trace.pattern_ms_per_step(run, part)


def roofline(run: dict, need_key: str, ms: Optional[float]
             ) -> Optional[float]:
    """The least time the chip could take for ``run["lm"][need_key]``
    (FLOPs over the bf16 peak or bytes over the HBM peak, whichever is
    larger) over ``ms``, in percent."""
    lm = run.get("lm") or {}
    if not ms or not lm.get("peaks") or need_key not in lm:
        return None
    need, peaks = lm[need_key], lm["peaks"]
    least_s = max(need["flops"] / peaks["bf16_flops_per_s"],
                  need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)


def mla_flash_ms_per_step(run: dict) -> Optional[float]:
    """The Pallas custom calls inside the MLA layers' ATTENTION scopes,
    replays included."""
    if not is_ours(run):
        return None
    return lm_trace.self_ms_per_step(
        run, lambda label, _, layer_type: layer_type == "ATTENTION"
        and device_trace.is_pallas(label))


def mla_glue_ms_per_step(run: dict) -> Optional[float]:
    """The ATTENTION scopes' time outside their Pallas calls, and the
    latent's split and norm."""
    if not is_ours(run):
        return None
    inside = lm_trace.self_ms_per_step(
        run, lambda label, _, layer_type: layer_type == "ATTENTION"
        and not device_trace.is_pallas(label))
    around = looplm_trace.pattern_ms_per_step(run, "mla_glue")
    if inside is None or around is None:
        return None
    return inside + around


def published(run: dict, key: str):
    """What the runner read off the program's own counters and display rows
    under ``key`` (``run["lm"]``), None in a run that is not this cell's."""
    return (run.get("lm") or {}).get(key) if is_ours(run) else None


def mean_of(run: dict, key: str) -> Optional[float]:
    values = published(run, key)
    return sum(values) / len(values) if values else None
