"""Device layer: share of the traced window in which no operation ran on the
chip (1 - union of op intervals / window; mean over chips)."""

import device_trace


def reduce(run: dict):
    devices = device_trace.traced_devices(run)
    if not devices:
        return None
    start, end = device_trace.window(devices)
    return 100.0 * (1.0 - device_trace.busy_seconds(devices)
                    / ((end - start) / 1e9))
