"""Kernels layer: share of the device-busy time spent in the program's Pallas
(Mosaic) kernels: custom calls whose target is ``tpu_custom_call``."""

import device_trace


def reduce(run: dict):
    devices = device_trace.traced_devices(run)
    if not devices:
        return None
    kernels = sum(
        device_trace.total(device_trace.union(
            op for op in ops if device_trace.is_pallas(op[0])))
        for ops in devices.values()) / len(devices) / 1e9
    return 100.0 * kernels / device_trace.busy_seconds(devices)
