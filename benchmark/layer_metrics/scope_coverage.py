"""Graph layer: share of the chip's busy time whose instruction is in the
program's own op -> (layer, pass) map (``scope_trace``). The honest
residual of the per-pass metrics: what is outside is named on stderr, never
matched in by pattern."""

import sys

import scope_trace


def reduce(run: dict):
    split = scope_trace.parts(run)
    if split is None or not split["busy_ms"]:
        return None
    worst = sorted(split["unmapped_ops"].items(), key=lambda kv: -kv[1])[:8]
    print("[scope_trace] ms/step "
          + str({k: round(v, 4)
                 for k, v in scope_trace.terms(split).items()})
          + "; unmapped, costliest first: "
          + ", ".join(f"{label} {ms:.4f}" for label, ms in worst),
          file=sys.stderr)
    return 100.0 * (1.0 - split["unmapped_ms"] / split["busy_ms"])
