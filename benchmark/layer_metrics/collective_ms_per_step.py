"""Step builder / sync layer: device milliseconds per step in cross-chip
collectives (union of their intervals on a chip's op line, mean over chips)."""

import device_trace


def per_step(run: dict, which: int):
    devices = device_trace.traced_devices(run)
    if not devices:
        return None
    trace = run["trace"]
    times = [device_trace.collective_time(ops, trace["async"].get(chip, ()))
             for chip, ops in devices.items()]
    if not any(t[0] for t in times):
        return None
    return sum(t[which] for t in times) / len(times) / 1e6 / trace["steps"]


def reduce(run: dict):
    return per_step(run, 0)
