"""Graph layer: operations the chip executed per step (events on the op line
that hold no other event; mean over chips)."""

import device_trace


def reduce(run: dict):
    devices = device_trace.traced_devices(run)
    if not devices:
        return None
    counts = [len(device_trace.leaves(ops)) for ops in devices.values()]
    return sum(counts) / len(counts) / run["trace"]["steps"]
