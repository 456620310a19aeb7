"""Train-loop layer: the longest gap between two consecutive steps'
completions over the median gap (``step_done`` instants, taken when a
step's metrics are host floats). 1 = an even pace; a stall of the loop, the
reader or the collector shows as one long gap."""

import host_spans


def reduce(run: dict):
    done = sorted(e["ts"] for e in host_spans.named(run, "step_done"))
    gaps = sorted(b - a for a, b in zip(done, done[1:]))
    if len(gaps) < 2 or gaps[len(gaps) // 2] <= 0:
        return None
    return gaps[-1] / gaps[len(gaps) // 2]
