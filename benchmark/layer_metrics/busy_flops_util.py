"""Kernels layer: the step's share of the compute roofline while it runs —
required FLOPs per step over (device-busy seconds per step x peak FLOP/s).
mfu_required = busy_flops_util x (1 - device_idle_share), to first order."""

import device_trace


def reduce(run: dict):
    devices = device_trace.traced_devices(run)
    if not devices or not run["peak_flops_per_s"]:
        return None
    step_flops = run["flops_per_image"] * run["batch_per_chip"]
    busy_per_step = device_trace.busy_seconds(devices) / run["trace"]["steps"]
    return 100.0 * step_flops / (busy_per_step * run["peak_flops_per_s"])
